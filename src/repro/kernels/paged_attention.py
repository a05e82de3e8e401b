"""Paged-attention decode kernel for TPU (Pallas, block-table indirection).

The serving engine's paged KV cache stores K/V in a *global page pool*
shared by every slot — per layer, ``(KH, num_pages, page, Dh)`` — and
each slot owns a row of a *page table* ``(B, max_pages)`` mapping its
logical page index ``j`` (token positions ``[j*page, (j+1)*page)``) to a
physical pool page.  Unused entries are ``-1``.  Decode attention then
reads a slot's KV through the indirection, so HBM scales with *live*
tokens (allocated pages) instead of ``max_batch × max_seq`` reservation.

The walk: grid ``(B, KH)``, one grid step per slot and KV head.  The
pools stay in HBM (``memory_space=pl.ANY``); the page table and the
per-slot lengths ride in as **scalar prefetch** operands in SMEM.  Each
grid step loops over *blocks* of ``ppb`` pages (:func:`pages_per_block`,
about 256 tokens), only as many blocks as the slot's live length needs:
a block's pages are gathered with one ``pltpu.make_async_copy`` per page
(``page_table[b, j]`` -> a ``(2, ppb, page, D)`` double buffer in VMEM),
the next block's copies start before the current block is computed, and
an online softmax folds the ``(rows, ppb * page)`` score tile into
float32 accumulators.  Past a slot's live length nothing is done: no
page past the last live one is copied, and no block past the last live
one is computed.  A slot whose first page slot is unmapped is parked
(the engine sets a retired slot's row to ``-1``) and walks nothing; a
row that walks nothing writes zeros.

One walk serves both entry points.  ``paged_attention_bkgd`` is
single-token decode (``rows = G`` queries per KV head);
``paged_attention_mq_bkgd`` is speculative verify, where the ``T``
draft positions ride in as extra q rows (row ``r`` = draft position
``r // G``) with per-row causal limits ``len + r // G``.  At ``T = 1``
the two are the same computation.

Layout notes: queries arrive as ``(B, KH, rows, Dh)`` and the pool's
trailing dims are ``(page, Dh)``, with ``Dh`` padded to 128 by the
``ops.py`` wrapper and ``page`` a power of two ≥ 8.  K/V are cast to
float32 in VMEM; scores, running max, denominator and accumulator are
float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_BLOCK_TOKENS = 256  # tokens gathered per compute block


def pages_per_block(page: int, max_pages: int) -> int:
    """Pages gathered per compute block: about ``_BLOCK_TOKENS`` tokens,
    at most the page table's width."""
    return max(1, min(max_pages, _BLOCK_TOKENS // page))


def _paged_walk_kernel(
    pt_ref,    # SMEM (B, max_pages) int32 page table, dead slots at 0
    len_ref,   # SMEM (B,) int32 kv length visible to query row 0
    q_ref,     # (1, 1, rows, D), rows = T * group
    k_hbm,     # (KH, P, page, D) page pool, left in HBM
    v_hbm,
    o_ref,     # (1, 1, rows, D)
    k_buf,     # VMEM (2, ppb, page, D) double-buffered gathered pages
    v_buf,
    sem,       # DMA semaphores (2, 2): [K or V, buffer]
    m_scr,     # VMEM (rows, 128) running max
    l_scr,     # VMEM (rows, 128) running denom
    acc_scr,   # VMEM (rows, D) accumulator
    *,
    scale: float,
    page: int,
    ppb: int,
    group: int,
):
    b = pl.program_id(0)
    h = pl.program_id(1)
    rows = q_ref.shape[2]
    bk = ppb * page
    base_len = len_ref[b]
    # positions the furthest-ahead draft row sees (base_len + T - 1),
    # within the table; an empty row (length 0) walks nothing
    kv_hi = jnp.where(
        base_len > 0,
        jnp.minimum(base_len + rows // group - 1, pt_ref.shape[1] * page), 0)
    n_pages = (kv_hi + page - 1) // page
    n_blocks = (n_pages + ppb - 1) // ppb

    def dma(blk, buf, start: bool):
        """Start (or wait for) the copies of block ``blk``'s live pages
        into buffer ``buf``; pages past ``n_pages`` are not copied."""
        for i in range(ppb):
            j = blk * ppb + i

            @pl.when(j < n_pages)
            def _():
                pid = pt_ref[b, j]
                for kv, (pool, dst) in enumerate(((k_hbm, k_buf),
                                                  (v_hbm, v_buf))):
                    c = pltpu.make_async_copy(pool.at[h, pid], dst.at[buf, i],
                                              sem.at[kv, buf])
                    if start:
                        c.start()
                    else:
                        c.wait()

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    dma(0, 0, True)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (rows, D)
    t_of_row = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0) // group
    limit = jnp.minimum(base_len + t_of_row, kv_hi)

    def body(blk, carry):
        buf = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _prefetch_next():
            dma(blk + 1, 1 - buf, True)

        dma(blk, buf, False)
        k = k_buf[buf].astype(jnp.float32).reshape(bk, -1)  # (bk, D)
        v = v_buf[buf].astype(jnp.float32).reshape(bk, -1)
        # rows of the buffer past the live pages hold no copy of this
        # block: zero them, so that p == 0 there cannot meet a NaN
        vpos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vpos < kv_hi, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (rows, bk)
        kpos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        s = jnp.where(kpos < limit, s, NEG_INF)

        m_prev = m_scr[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    denom = jnp.maximum(l_scr[:, 0], 1e-30)
    o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def _paged_walk(q, k_pool, v_pool, page_table, lens, *, scale, page, group,
                interpret, name):
    B, KH, rows, D = q.shape
    ppb = pages_per_block(page, page_table.shape[1])

    # Dead entries (-1) point at page 0, so every issued DMA reads a
    # valid pool page (a live position in an unmapped page slot reads the
    # null page, as the oracle does).  A parked row (page slot 0
    # unmapped) gets length 0.
    pt = jnp.maximum(page_table, 0).astype(jnp.int32)
    lens = jnp.where(page_table[:, 0] >= 0, lens, 0).astype(jnp.int32)

    kernel = functools.partial(
        _paged_walk_kernel, scale=scale, page=page, ppb=ppb, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KH),
        in_specs=[
            pl.BlockSpec((1, 1, rows, D), lambda b, h, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, D),
                               lambda b, h, pt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, D), k_pool.dtype),
            pltpu.VMEM((2, ppb, page, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, rows, D), q.dtype),
        interpret=interpret,
        name=name,
    )(pt, lens, q, k_pool, v_pool)


@functools.partial(
    jax.jit, static_argnames=("scale", "page", "group", "interpret")
)
def paged_attention_mq_bkgd(
    q: jax.Array,           # (B, KH, T*G, D)   D % 128 == 0
    k_pool: jax.Array,      # (KH, P, page, D) global page pool
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32, -1 = unmapped
    base_len: jax.Array,    # (B,) int32 kv length visible to draft row 0
    *,
    scale: float,
    page: int,
    group: int,
    interpret: bool = False,
) -> jax.Array:
    """Speculative-verify paged attention: the walk of
    :func:`paged_attention_bkgd` with the q tile widened over the
    ``k+1`` draft positions and per-row causal limits (query row ``r``
    attends positions ``< base_len[b] + r // group``)."""
    return _paged_walk(q, k_pool, v_pool, page_table, base_len, scale=scale,
                       page=page, group=group, interpret=interpret,
                       name="paged_attention_mq_bkgd")


@functools.partial(
    jax.jit, static_argnames=("scale", "page", "interpret")
)
def paged_attention_bkgd(
    q: jax.Array,           # (B, KH, G, D)   D % 128 == 0
    k_pool: jax.Array,      # (KH, P, page, D) global page pool
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32, -1 = unmapped
    kv_len: jax.Array,      # (B,) int32 live length per slot
    *,
    scale: float,
    page: int,
    interpret: bool = False,
) -> jax.Array:
    """Single-token paged decode attention: each slot's ``G`` queries
    per KV head attend its first ``kv_len[b]`` cached positions."""
    return _paged_walk(q, k_pool, v_pool, page_table, kv_len, scale=scale,
                       page=page, group=q.shape[2], interpret=interpret,
                       name="paged_attention_bkgd")
