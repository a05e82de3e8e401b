"""Flash attention for TPU (Pallas, online softmax, GQA, sliding window).

Layout: inputs are pre-transposed to (B, H, S, D) / (B, KH, T, D) by the
``ops.py`` wrapper, with D padded to a multiple of 128 (MXU lane width) and
S/T padded to the block size.  Grid is (B, H, num_q_blocks, num_kv_blocks)
with the kv dimension innermost: TPU grids execute sequentially over the
last axis, so the online-softmax accumulators live in VMEM scratch and are
initialized at kv_idx == 0 and flushed to the output block at the final kv
step.  Fully-masked (q, kv) block pairs are skipped via ``pl.when``.

VMEM working set per grid step (block_q = block_k = 256, D = 128, fp32):
q 128 KiB + k 128 KiB + v 128 KiB + acc 128 KiB + scores 256 KiB ≈ 0.8 MiB,
comfortably inside a v5e core's VMEM while leaving room for double
buffering of the k/v streams.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, 1, block_q, D)
    k_ref,  # (1, 1, block_k, D)
    v_ref,  # (1, 1, block_k, D)
    o_ref,  # (1, 1, block_q, D)
    *refs,
    scale: float,
    causal: bool,
    window: int,
    q_offset: int,
    block_q: int,
    block_k: int,
    kv_seq: int,
    num_kv_blocks: int,
):
    # refs: [lse_ref (1, 1, block_q, 128) when requested], then VMEM
    # scratch m (block_q, 128) running max and l (block_q, 128) running
    # denom, both broadcast along lanes, and acc (block_q, D)
    lse_ref = refs[0] if len(refs) == 4 else None
    m_scr, l_scr, acc_scr = refs[-3:]
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + qi * block_q
    k_start = ki * block_k

    # Block-level skip: no (q, k) pair in this tile can be live.
    conds = []
    if causal:
        conds.append(q_start + block_q - 1 >= k_start)  # some pair is causal-live
    if window > 0:
        conds.append(q_start - (k_start + block_k - 1) < window)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_seq  # padding mask
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]  # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)

    if conds:
        live = conds[0]
        for c in conds[1:]:
            live = jnp.logical_and(live, c)
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))


@functools.partial(
    jax.jit,
    static_argnames=(
        "kv_seq", "scale", "causal", "window", "q_offset", "block_q",
        "block_k", "return_lse", "interpret",
    ),
)
def flash_attention_bhsd(
    q: jax.Array,  # (B, H, S, D)  D % 128 == 0, S % block_q == 0
    k: jax.Array,  # (B, KH, T, D) T % block_k == 0
    v: jax.Array,
    *,
    kv_seq: int,  # true (unpadded) kv length
    scale: float,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 256,
    block_k: int = 256,
    return_lse: bool = False,
    interpret: bool = False,
):
    """Returns the attention output, or ``(out, lse)`` with
    ``return_lse``: the per-row log-sum-exp of the scaled scores,
    ``(B, H, S)`` float32, which the backward pass needs."""
    B, H, S, D = q.shape
    KH, T = k.shape[1], k.shape[2]
    group = H // KH
    nq, nk = S // block_q, T // block_k
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        kv_seq=kv_seq,
        num_kv_blocks=nk,
    )
    out_specs = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    out_shape = jax.ShapeDtypeStruct((B, H, S, D), q.dtype)
    if return_lse:
        # lane-broadcast like the m/l scratch: a (block_q, 128) tile
        out_specs = [out_specs, pl.BlockSpec((1, 1, block_q, 128),
                                             lambda b, h, i, j: (b, h, i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, H, S, 128), jnp.float32)]
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if return_lse:
        out, lse = res
        return out, lse[..., 0]
    return res
