"""Grouped (ragged) matmul over expert-sorted tokens for TPU Pallas.

MegaBlocks-style MoE expert compute without capacity padding: tokens are
pre-sorted so expert ``e`` owns the contiguous row range
[starts[e], starts[e] + sizes[e]).  The kernel walks (token-tile × expert)
pairs; each token tile accumulates contributions from every expert whose
range intersects it (at most a few), masking rows outside the range.  Tiles
fully outside an expert's range are skipped with ``pl.when`` so the steady
state is one (block_m × D) · (D × F) MXU matmul per live pair.

Grid: (M/block_m, F/block_f, E, D/block_d) — the expert and contraction
axes innermost/sequential; the (block_m, block_f) accumulator tile lives
in VMEM scratch, flushed after the last (expert, D-block) pair.  Tiling
F and D keeps the weight block at (block_d, block_f) instead of a whole
(D, F) expert, which at phi3.5-moe widths (4096 × 6400) is far larger
than VMEM.

Group offsets arrive via scalar-prefetch (SMEM) so index maps stay static.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(
    starts_ref,  # SMEM (E,) i32 — scalar prefetch
    ends_ref,  # SMEM (E,) i32 — scalar prefetch
    x_ref,  # (block_m, block_d)
    w_ref,  # (1, block_d, block_f)
    o_ref,  # (block_m, block_f)
    acc_scr,  # VMEM (block_m, block_f) f32
    *,
    block_m: int,
    num_experts: int,
    num_d_blocks: int,
):
    ti = pl.program_id(0)
    e = pl.program_id(2)
    di = pl.program_id(3)

    @pl.when(jnp.logical_and(e == 0, di == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    row0 = ti * block_m
    start = starts_ref[e]
    end = ends_ref[e]
    live = jnp.logical_and(row0 < end, row0 + block_m > start)

    @pl.when(live)
    def _compute():
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
        mask = jnp.logical_and(rows >= start, rows < end)  # (block_m, 1)
        x = jnp.where(mask, x_ref[...].astype(jnp.float32), 0.0)
        w = w_ref[0].astype(jnp.float32)  # (block_d, block_f)
        acc_scr[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(e == num_experts - 1, di == num_d_blocks - 1))
    def _flush():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def _lane_block(n: int, cap: int = 512) -> int:
    """Largest multiple of 128 that is at most ``cap`` and divides ``n``;
    ``n`` itself (a whole-dimension block) when there is none."""
    for b in range(cap - cap % 128, 0, -128):
        if n % b == 0:
            return b
    return n


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def moe_gmm_sorted(
    tokens: jax.Array,  # (M, D) expert-sorted
    group_sizes: jax.Array,  # (E,) i32
    w: jax.Array,  # (E, D, F)
    *,
    block_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    M, D = tokens.shape
    E, _, F = w.shape
    assert M % block_m == 0, (M, block_m)
    bf, bd = _lane_block(F), _lane_block(D)
    sizes = group_sizes.astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    ends = starts + sizes

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // block_m, F // bf, E, D // bd),
        in_specs=[
            pl.BlockSpec((block_m, bd), lambda t, f, e, d, starts, ends: (t, d)),
            pl.BlockSpec((1, bd, bf), lambda t, f, e, d, starts, ends: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((block_m, bf), lambda t, f, e, d, starts, ends: (t, f)),
        scratch_shapes=[pltpu.VMEM((block_m, bf), jnp.float32)],
    )
    kernel = functools.partial(_gmm_kernel, block_m=block_m, num_experts=E,
                               num_d_blocks=D // bd)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, F), tokens.dtype),
        interpret=interpret,
    )(starts, ends, tokens, w)
