"""Mamba-style selective state-space scan for TPU Pallas.

    h_t = exp(dt_t ⊙ A) h_{t-1} + (dt_t ⊙ x_t) ⊗ B_t
    y_t = h_t · C_t + D ⊙ x_t

The time recurrence is sequential; channels are embarrassingly parallel.
TPU adaptation: tile the channel dimension across the grid (each grid row
owns a (block_d, N) state slab resident in VMEM) and walk the sequence in
chunks along the innermost (sequential) grid axis, with an inner
``fori_loop`` over the chunk's timesteps, eight at a time (one aligned
sublane tile per load and store).  All per-step work is VPU
elementwise + a tiny (block_d × N) reduction — the kernel exists to keep
the state in VMEM across the whole sequence instead of bouncing it to HBM
every step (the XLA scan fallback does exactly that bounce).

Grid: (B, Din/block_d, S/chunk) — chunk axis innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ROWS = 8  # timesteps per aligned load/store: one sublane tile


def _ssm_kernel(
    x_ref,  # (1, chunk, bd)
    dt_ref,  # (1, chunk, bd)
    A_ref,  # (bd, N)
    B_ref,  # (1, chunk, N)
    C_ref,  # (1, chunk, N)
    y_ref,  # out (1, chunk, bd)
    h_scr,  # VMEM (bd, N) f32
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = A_ref[...].astype(jnp.float32)  # (bd, N)
    lane = jax.lax.broadcasted_iota(jnp.int32, (A.shape[0], ROWS), 1)

    def group(g, h):
        # Reads and writes move ROWS timesteps at a sublane-aligned
        # offset; the steps inside the group are unrolled.  Channels go
        # to sublanes ((bd, ROWS) after the transpose) so each step's
        # (bd, 1) column broadcasts against the (bd, N) state.
        t0 = pl.multiple_of(g * ROWS, ROWS)
        xs = x_ref[0, pl.ds(t0, ROWS), :].astype(jnp.float32).T  # (bd, ROWS)
        dts = dt_ref[0, pl.ds(t0, ROWS), :].astype(jnp.float32).T
        bs = B_ref[0, pl.ds(t0, ROWS), :].astype(jnp.float32)  # (ROWS, N)
        cs = C_ref[0, pl.ds(t0, ROWS), :].astype(jnp.float32)
        ys = jnp.zeros(lane.shape, jnp.float32)
        for j in range(ROWS):
            dtj = dts[:, j:j + 1]  # (bd, 1)
            h = jnp.exp(dtj * A) * h + (dtj * xs[:, j:j + 1]) * bs[j:j + 1, :]
            yj = jnp.sum(h * cs[j:j + 1, :], axis=-1, keepdims=True)  # (bd, 1)
            ys = jnp.where(lane == j, yj, ys)
        y_ref[0, pl.ds(t0, ROWS), :] = ys.T.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // ROWS, group, h_scr[...])


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def ssm_scan_bsd(
    x: jax.Array,  # (B, S, Din)
    dt: jax.Array,  # (B, S, Din)
    A: jax.Array,  # (Din, N)
    Bmat: jax.Array,  # (B, S, N)
    Cmat: jax.Array,  # (B, S, N)
    D: jax.Array,  # (Din,)
    *,
    block_d: int = 256,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    Bsz, S, Din = x.shape
    N = A.shape[-1]
    assert Din % block_d == 0 and S % chunk == 0 and chunk % ROWS == 0, (
        Din, block_d, S, chunk)
    grid = (Bsz, Din // block_d, S // chunk)
    kernel = functools.partial(_ssm_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((block_d, N), lambda b, d, c: (d, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((Bsz, S, Din), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bmat, Cmat)
    return y + x * D[None, None, :].astype(x.dtype)
