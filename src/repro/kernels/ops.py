"""jit'd public wrappers around the Pallas kernels and their oracles.

Backend (resolved from the device on first use, see :func:`get_backend`):

  * ``tpu``       — compiled Pallas; chosen when ``jax.default_backend()``
                    is ``"tpu"``
  * ``ref``       — pure-jnp oracle / XLA paths; chosen on every other
                    backend (CPU, dry-run lowering)
  * ``interpret`` — Pallas kernels executed with ``interpret=True`` (CPU
                    correctness validation of the TPU kernel bodies); only
                    ever set explicitly by tests through :func:`set_backend`

On ``tpu``/``interpret`` a kernel never gives way to its oracle:
``flash_attention`` differentiates through a custom VJP, and the kernels
without a backward raise under ``jax.grad``.

Wrappers own all layout plumbing (BSHD↔BHSD transposes, lane padding to
128, block padding) so both kernel and oracle see hardware-friendly
shapes.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.parallel import hints
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.mlstm_scan import mlstm_scan_bhsd
from repro.kernels.ssm_scan import ssm_scan_bsd
from repro.kernels.moe_gmm import moe_gmm_sorted

_BACKEND: Optional[str] = None  # resolved lazily by get_backend()
_VALID = ("ref", "interpret", "tpu")
_ATTN_IMPL = os.environ.get("REPRO_ATTN_IMPL", "xla")  # xla | tri
_SSM_CHUNK = 0  # 0 = per-step oracle scan; >0 = chunked fallback
_FLASH_BQ, _FLASH_BK = 512, 1024


def set_ssm_chunk(chunk: int) -> None:
    global _SSM_CHUNK
    _SSM_CHUNK = int(chunk)


def set_flash_blocks(bq: int, bk: int) -> None:
    global _FLASH_BQ, _FLASH_BK
    _FLASH_BQ, _FLASH_BK = int(bq), int(bk)


def set_attn_impl(name: str) -> None:
    global _ATTN_IMPL
    if name not in ("xla", "tri"):
        raise ValueError(name)
    _ATTN_IMPL = name


def get_attn_impl() -> str:
    return _ATTN_IMPL


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in _VALID:
        raise ValueError(f"backend {name!r} not in {_VALID}")
    _BACKEND = name


def get_backend() -> str:
    """The kernel backend in use: ``tpu`` on a TPU, ``ref`` elsewhere,
    unless :func:`set_backend` chose one.  Resolved on first use (not at
    import), so importing this module never initializes a device."""
    global _BACKEND
    if _BACKEND is None:
        _BACKEND = "tpu" if jax.default_backend() == "tpu" else "ref"
    return _BACKEND


def _forward_only(name: str, fn):
    """Wrap a Pallas kernel call that has no backward: the primal runs
    unchanged, and differentiating it raises instead of silently
    differentiating something else."""

    @jax.custom_vjp
    def op(*args):
        return fn(*args)

    def fwd(*args):
        raise NotImplementedError(
            f"{name}: the Pallas kernel (backend {get_backend()!r}) has no "
            f"backward; it cannot be differentiated")

    op.defvjp(fwd, lambda res, g: None)
    return op


def _pad_to(x: jax.Array, axis: int, mult: int) -> Tuple[jax.Array, int]:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


# --------------------------------------------------------------------------
def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, KH, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 256,
    block_k: int = 256,
) -> jax.Array:
    """Attention over a whole sequence (train step, prefill).

    ``ref``: the oracle for small S·T, else the XLA flash scan.
    ``tpu``/``interpret``: the Pallas kernel under a ``jax.custom_vjp``
    whose forward is ``flash_attention_bhsd`` and whose backward is the
    XLA flash backward of ``flash_xla.flash_attention_xla``, fed the
    kernel's (out, lse) residuals.  Under an installed mesh the kernel
    runs per data shard (``hints.shard_batch``)."""
    backend = get_backend()
    if backend == "ref":
        S, T = q.shape[1], k.shape[1]
        if S * T <= 1024 * 1024:
            return ref.attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
        if _ATTN_IMPL == "tri":
            from repro.kernels.flash_tri import flash_attention_tri

            return flash_attention_tri(q, k, v, causal, window, q_offset,
                                       _FLASH_BQ, _FLASH_BK)
        from repro.kernels.flash_xla import flash_attention_xla

        return flash_attention_xla(q, k, v, causal, window, q_offset,
                                   _FLASH_BQ, _FLASH_BK)

    bq = block_q if q.shape[1] >= block_q else q.shape[1]
    bk = block_k if k.shape[1] >= block_k else k.shape[1]
    return hints.shard_batch(
        lambda q, k, v: _flash_pallas(q, k, v, causal, window, q_offset, bq,
                                      bk, backend == "interpret"))(q, k, v)


def _flash_pallas_call(q, k, v, causal, window, q_offset, bq, bk, interpret,
                       return_lse=False):
    """Pallas forward in the (B, S, H, D) layout.  With ``return_lse``
    returns (out, lse), ``lse`` (B, S, KH, G) float32 as ``flash_xla``
    lays it out."""
    B, S, H, D = q.shape
    KH = k.shape[2]
    qt = jnp.swapaxes(q, 1, 2)  # (B, H, S, D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    qt, _ = _pad_to(qt, 3, 128)
    kt, _ = _pad_to(kt, 3, 128)
    vt, _ = _pad_to(vt, 3, 128)
    qt, s_orig = _pad_to(qt, 2, bq)
    kt, t_orig = _pad_to(kt, 2, bk)
    vt, _ = _pad_to(vt, 2, bk)

    res = flash_attention_bhsd(
        qt, kt, vt,
        kv_seq=t_orig, scale=D ** -0.5, causal=causal, window=window,
        q_offset=q_offset, block_q=bq, block_k=bk, return_lse=return_lse,
        interpret=interpret,
    )
    if not return_lse:
        return jnp.swapaxes(res[:, :, :s_orig, :D], 1, 2)
    out, lse = res
    out = jnp.swapaxes(out[:, :, :s_orig, :D], 1, 2)
    lse = jnp.swapaxes(lse[:, :, :s_orig], 1, 2).reshape(B, S, KH, H // KH)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_pallas(q, k, v, causal, window, q_offset, bq, bk, interpret):
    return _flash_pallas_call(q, k, v, causal, window, q_offset, bq, bk,
                              interpret)


def _flash_pallas_vjp_fwd(q, k, v, causal, window, q_offset, bq, bk,
                          interpret):
    out, lse = _flash_pallas_call(q, k, v, causal, window, q_offset, bq, bk,
                                  interpret, return_lse=True)
    return out, (q, k, v, out, lse)


def _flash_pallas_vjp_bwd(causal, window, q_offset, bq, bk, interpret, res,
                          do):
    from repro.kernels.flash_xla import _bwd_vjp

    # blocked by set_flash_blocks, like the ref backend's XLA flash
    return _bwd_vjp(causal, window, q_offset, _FLASH_BQ, _FLASH_BK, res, do)


_flash_pallas.defvjp(_flash_pallas_vjp_fwd, _flash_pallas_vjp_bwd)


def decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    k: jax.Array,  # (B, T, KH, D) — cache
    v: jax.Array,
    *,
    kv_len: jax.Array,  # (B,) valid lengths
    window: int = 0,
) -> jax.Array:
    """Single-token attention against a cache.  XLA handles this well (it
    is a bandwidth-bound matvec); all backends use the oracle path."""
    return ref.attention(q, k, v, causal=False, window=0, kv_len=kv_len)


def decode_attention_mq(
    q: jax.Array,         # (B, T, H, D) — T = k+1 speculative positions
    k: jax.Array,         # (B, S_max, KH, D) — cache (draft rows written)
    v: jax.Array,
    *,
    base_len: jax.Array,  # (B,) kv length visible to query row 0
) -> jax.Array:
    """Multi-query decode attention for speculative verify: query row
    ``t`` attends cache positions ``< base_len[b] + t`` (per-row causal
    limits).  Small caches take the dense oracle; big ones the XLA
    online-softmax scan (``flash_xla.decode_attention_mq_xla``) so the
    ``(B, T, S_max)`` score tensor is never materialized."""
    B, S, _, _ = q.shape
    T = k.shape[1]
    if B * S * T <= 256 * 1024:
        return ref.decode_attention_mq(q, k, v, base_len)
    from repro.kernels.flash_xla import decode_attention_mq_xla

    return decode_attention_mq_xla(q, k, v, base_len)


def paged_decode_attention_mq(
    q: jax.Array,           # (B, T, H, D) — T = k+1 speculative positions
    k_pool: jax.Array,      # (KH, P, page, D) global page pool
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32, -1 = unmapped
    *,
    base_len: jax.Array,    # (B,) kv length visible to query row 0
) -> jax.Array:
    """Speculative verify through the page-table indirection.

    ``ref`` backend: dense-gather oracle for small tables, the scanned
    XLA online-softmax fallback for big ones.  ``interpret``/``tpu``:
    the Pallas multi-query kernel
    (``paged_attention.paged_attention_mq_bkgd``) — same block-table
    scalar prefetch as the single-token kernel, q tile widened over the
    ``k+1`` draft positions."""
    B, T, H, D = q.shape
    KH, _, page, _ = k_pool.shape
    max_pages = page_table.shape[1]
    backend = get_backend()
    if backend == "ref":
        if B * max_pages * page <= 256 * 1024:
            return ref.paged_attention_mq(q, k_pool, v_pool, page_table,
                                          base_len)
        from repro.kernels.flash_xla import paged_attention_mq_xla

        return paged_attention_mq_xla(q, k_pool, v_pool, page_table, base_len)

    from repro.kernels.paged_attention import paged_attention_mq_bkgd

    G = H // KH
    # rows = t*G + g so the kernel recovers the draft position as row//G
    qt = q.reshape(B, T, KH, G, D).transpose(0, 2, 1, 3, 4)
    qt = qt.reshape(B, KH, T * G, D)
    qt, _ = _pad_to(qt, 3, 128)
    kp, _ = _pad_to(k_pool, 3, 128)
    vp, _ = _pad_to(v_pool, 3, 128)
    out = paged_attention_mq_bkgd(
        qt, kp, vp, page_table, base_len,
        scale=D ** -0.5, page=page, group=G,
        interpret=(backend == "interpret"),
    )
    out = out[..., :D].reshape(B, KH, T, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, D)


def paged_decode_attention(
    q: jax.Array,           # (B, 1, H, D)
    k_pool: jax.Array,      # (KH, P, page, D) global page pool
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32, -1 = unmapped
    *,
    kv_len: jax.Array,      # (B,) live lengths
) -> jax.Array:
    """Single-token attention through a page-table indirection.

    ``ref`` backend: the dense-gather oracle for small tables, the
    scanned XLA online-softmax fallback for big ones (never materializes
    the gathered cache).  ``interpret``/``tpu``: the Pallas kernel
    (``paged_attention_bkgd``) with the page table as scalar prefetch.
    """
    B, _, H, D = q.shape
    KH, _, page, _ = k_pool.shape
    max_pages = page_table.shape[1]
    backend = get_backend()
    if backend == "ref":
        if B * max_pages * page <= 256 * 1024:
            return ref.paged_attention(q, k_pool, v_pool, page_table, kv_len)
        from repro.kernels.flash_xla import paged_attention_xla

        return paged_attention_xla(q, k_pool, v_pool, page_table, kv_len)

    from repro.kernels.paged_attention import paged_attention_bkgd

    G = H // KH
    qt = q.reshape(B, 1, KH, G, D)[:, 0]         # (B, KH, G, D)
    qt, _ = _pad_to(qt, 3, 128)
    kp, _ = _pad_to(k_pool, 3, 128)
    vp, _ = _pad_to(v_pool, 3, 128)
    out = paged_attention_bkgd(
        qt, kp, vp, page_table, kv_len,
        scale=D ** -0.5, page=page,
        interpret=(backend == "interpret"),
    )
    return out[..., :D].reshape(B, 1, H, D)


# --------------------------------------------------------------------------
def mlstm_scan(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,
    v: jax.Array,
    i_pre: jax.Array,  # (B, H, S)
    f_pre: jax.Array,
    *,
    chunk: int = 256,
) -> jax.Array:
    backend = get_backend()
    if backend == "ref":
        h, _ = ref.mlstm_scan(q, k, v, i_pre, f_pre)
        return h
    S = q.shape[2]
    c = min(chunk, S)
    qp, s_orig = _pad_to(q, 2, c)
    kp, _ = _pad_to(k, 2, c)
    vp, _ = _pad_to(v, 2, c)
    # padded steps: i gate -> -inf (no contribution), f gate -> +large (keep state)
    pad = qp.shape[2] - S
    if pad:
        ip = jnp.pad(i_pre, ((0, 0), (0, 0), (0, pad)), constant_values=-1e30)
        fp = jnp.pad(f_pre, ((0, 0), (0, 0), (0, pad)), constant_values=30.0)
    else:
        ip, fp = i_pre, f_pre
    kernel = functools.partial(mlstm_scan_bhsd, chunk=c,
                               interpret=(backend == "interpret"))
    h = _forward_only("mlstm_scan", kernel)(qp, kp, vp, ip, fp)
    return h[:, :, :s_orig]


def mlstm_step(q, k, v, i_pre, f_pre, state):
    """Single-token recurrent step (decode path — oracle recurrence)."""
    h, state = ref.mlstm_scan(
        q[:, :, None, :] if q.ndim == 3 else q,
        k[:, :, None, :] if k.ndim == 3 else k,
        v[:, :, None, :] if v.ndim == 3 else v,
        i_pre[..., None] if i_pre.ndim == 2 else i_pre,
        f_pre[..., None] if f_pre.ndim == 2 else f_pre,
        initial=state,
    )
    return h[:, :, 0, :], state


# --------------------------------------------------------------------------
def ssm_scan(
    x: jax.Array,  # (B, S, Din)
    dt: jax.Array,
    A: jax.Array,
    Bmat: jax.Array,
    Cmat: jax.Array,
    D: jax.Array,
    *,
    block_d: int = 256,
    chunk: int = 128,
) -> jax.Array:
    backend = get_backend()
    if backend == "ref":
        if _SSM_CHUNK > 0:
            from repro.kernels.ssm_vjp import ssm_scan_ckpt

            return ssm_scan_ckpt(x, dt, A, Bmat, Cmat, D, _SSM_CHUNK)
        y, _ = ref.ssm_scan(x, dt, A, Bmat, Cmat, D)
        return y
    Bsz, S, Din = x.shape
    bd = min(block_d, Din)
    c = min(chunk, -(-S // 8) * 8)  # the kernel moves 8 steps at a time
    xp, d_orig = _pad_to(x, 2, bd)
    dtp, _ = _pad_to(dt, 2, bd)
    Ap, _ = _pad_to(A, 0, bd)
    xp, s_orig = _pad_to(xp, 1, c)
    dtp, _ = _pad_to(dtp, 1, c)
    Bp, _ = _pad_to(Bmat, 1, c)
    Cp, _ = _pad_to(Cmat, 1, c)
    Dp, _ = _pad_to(D, 0, bd)
    kernel = functools.partial(ssm_scan_bsd, block_d=bd, chunk=c,
                               interpret=(backend == "interpret"))
    y = _forward_only("ssm_scan", kernel)(xp, dtp, Ap, Bp, Cp, Dp)
    return y[:, :s_orig, :d_orig]


def ssm_scan_with_state(x, dt, A, Bmat, Cmat, D):
    """Prefill path: returns (y, final_state); honors the chunked
    fallback knob (Pallas kernel path is train-oriented and stateless)."""
    if _SSM_CHUNK > 0:
        return ref.ssm_scan_chunked(x, dt, A, Bmat, Cmat, D, _SSM_CHUNK)
    return ref.ssm_scan(x, dt, A, Bmat, Cmat, D)


def ssm_step(x, dt, A, Bmat, Cmat, D, state):
    """Single-token recurrent step for decode.  x,dt: (B, Din); B,C: (B, N)."""
    y, state = ref.ssm_scan(
        x[:, None], dt[:, None], A, Bmat[:, None], Cmat[:, None], D, initial=state
    )
    return y[:, 0], state


# --------------------------------------------------------------------------
def moe_gmm(
    tokens: jax.Array,  # (M, D) expert-sorted
    group_sizes: jax.Array,  # (E,)
    w: jax.Array,  # (E, D, F)
    *,
    block_m: int = 256,
) -> jax.Array:
    backend = get_backend()
    if backend == "ref":
        return ref.moe_gmm(tokens, group_sizes, w)
    tp, m_orig = _pad_to(tokens, 0, block_m)
    kernel = functools.partial(moe_gmm_sorted, block_m=block_m,
                               interpret=(backend == "interpret"))
    out = _forward_only("moe_gmm", kernel)(tp, group_sizes, w)
    return out[:m_orig]
