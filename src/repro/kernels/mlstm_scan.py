"""Chunkwise-parallel mLSTM (xLSTM matrix-memory cell) for TPU Pallas.

The sequential recurrence

    m_t = max(log f_t + m_{t-1}, log i_t)
    C_t = e^{log f_t + m_{t-1} - m_t} C_{t-1} + e^{log i_t - m_t} k_t v_t^T
    n_t = e^{log f_t + m_{t-1} - m_t} n_{t-1} + e^{log i_t - m_t} k_t
    h_t = (q_t C_t) / max(|q_t · n_t|, e^{-m_t}) / sqrt(D)

is evaluated one *chunk* at a time: intra-chunk interactions are a masked
(L × L) matmul on the MXU (attention-like), while inter-chunk state (C, n,
m) is carried in f32 VMEM scratch across the sequential chunk grid axis.
This is the TPU-native adaptation: instead of a warp-level scan (GPU), the
chunk matmuls saturate the MXU and the scan granularity matches VMEM
residency.

Grid: (B, H, num_chunks) — num_chunks innermost/sequential.
VMEM per step (L=256, D=128): q/k/v 3·128 KiB + C 64 KiB + D-matrix
256 KiB ≈ 0.7 MiB.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mlstm_kernel(
    q_ref,  # (1, 1, L, D)
    k_ref,
    v_ref,  # (1, 1, L, DV)
    cumf_c_ref,  # (1, 1, L, 1) chunk-local inclusive cumsum of log f
    lmax_c_ref,  # (1, 1, L, 1) cummax_{j<=t}(log i_j - cumf_j) + cumf_t
    logi_c_ref,  # (1, 1, L, 1) log i
    cumf_r_ref,  # (1, 1, 1, L) cumf as a row
    logi_r_ref,  # (1, 1, 1, L) log i as a row
    h_ref,  # out (1, 1, L, DV)
    C_scr,  # VMEM (D, DV) f32
    n_scr,  # VMEM (1, D) f32
    m_scr,  # VMEM (1, 128) f32
    *,
    scale: float,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        C_scr[...] = jnp.zeros_like(C_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    L = chunk
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (L, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (L, D)
    v = v_ref[0, 0].astype(jnp.float32)  # (L, DV)
    cumf_c = cumf_c_ref[0, 0]  # (L, 1)
    logi_c = logi_c_ref[0, 0]
    cumf_r = cumf_r_ref[0, 0]  # (1, L)
    logi_r = logi_r_ref[0, 0]

    m_prev = m_scr[:, 0:1]  # (1, 1)
    C_prev = C_scr[...]
    n_prev = n_scr[...]  # (1, D)

    m_t = jnp.maximum(m_prev + cumf_c, lmax_c_ref[0, 0])  # (L, 1)

    # ---- inter-chunk contribution -------------------------------------
    inter_w = jnp.exp(m_prev + cumf_c - m_t)  # (L, 1)
    h_inter = jax.lax.dot_general(
        q, C_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * inter_w  # (L, DV)
    qn_inter = jax.lax.dot_general(
        q, n_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * inter_w  # (L, 1)

    # ---- intra-chunk contribution (masked attention-like) -------------
    # W[t, j] = exp(cumf_t - cumf_j + log_i_j - m_t) for j <= t
    logw = cumf_c - cumf_r + logi_r - m_t  # (L, L)
    tidx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jidx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    w = jnp.where(tidx >= jidx, jnp.exp(logw), 0.0)  # (L, L)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * w  # (L, L)
    h_intra = jax.lax.dot_general(
        s, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    qn_intra = jnp.sum(s, axis=-1, keepdims=True)  # (L, 1)

    denom = jnp.maximum(jnp.abs(qn_inter + qn_intra), jnp.exp(-m_t))
    h_ref[0, 0] = ((h_inter + h_intra) / denom).astype(h_ref.dtype)

    # ---- carry update ---------------------------------------------------
    m_end = m_t[L - 1:L, :]  # (1, 1)
    cumf_end = cumf_c[L - 1:L, :]
    # decay of old state across the whole chunk
    c_decay = jnp.exp(m_prev + cumf_end - m_end)
    # per-step weights into the end-of-chunk state
    wk = jnp.exp(cumf_end - cumf_c + logi_c - m_end)  # (L, 1)
    kw = k * wk  # (L, D)
    C_scr[...] = c_decay * C_prev + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (D, DV)
    n_scr[...] = c_decay * n_prev + jnp.sum(kw, axis=0, keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_end, m_scr.shape)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def mlstm_scan_bhsd(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,
    v: jax.Array,  # (B, H, S, DV)
    i_pre: jax.Array,  # (B, H, S)
    f_pre: jax.Array,  # (B, H, S)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, D = q.shape
    DV = v.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    # Gate prefix terms are chunk-local scans over the time axis: cheap
    # in XLA, and the kernel then needs them only as (L, 1) columns and
    # (1, L) rows, which keeps every block within the TPU's 8x128 tiling.
    log_i = i_pre.astype(jnp.float32).reshape(B, H, nc, chunk)
    log_f = jax.nn.log_sigmoid(f_pre.astype(jnp.float32)).reshape(B, H, nc, chunk)
    cumf = jnp.cumsum(log_f, axis=-1)
    lmax = jax.lax.cummax(log_i - cumf, axis=3) + cumf
    col = lambda x: x.reshape(B, H, S, 1)
    row = lambda x: x.reshape(B, H, 1, S)
    col_spec = pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0))
    row_spec = pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c))
    kernel = functools.partial(_mlstm_kernel, scale=D ** -0.5, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, DV), lambda b, h, c: (b, h, c, 0)),
            col_spec, col_spec, col_spec, row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, DV), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, DV), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((D, DV), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, col(cumf), col(lmax), col(log_i), row(cumf), row(log_i))
