"""Serving engine: slot-based continuous batching over the model decode
paths, with a fused on-device hot loop.

Design (vLLM-style, adapted to a static-shape JAX world):
  * the engine owns a fixed decode batch of ``max_batch`` slots and one
    jitted decode step for the whole batch — XLA-friendly static shapes;
  * new requests are admitted in *batches*: up to ``free_slots`` queued
    requests are prefilled in one jitted call (rows padded to a power-of
    -two bucket so retraces stay bounded) and scattered into the batched
    cache by a jitted slot writer — no per-request tree surgery;
  * finished sequences (EOS / max_tokens) free their slot immediately, so
    the decode batch continuously refills — no head-of-line blocking;
  * sampling is **fused into the jitted decode step**
    (:meth:`repro.models.api.Model.decode_and_sample`): the whole batch
    is argmaxed / categorical-sampled on device with a per-slot
    temperature vector and per-slot PRNG fold-in, so each engine
    ``step()`` transfers one ``(B,)`` int32 token array to the host —
    never the ``(B, V)`` logits;
  * ``decode_chunk > 1`` turns on chunked multi-token decode: a
    ``jax.lax.scan`` emits ``chunk × (B,)`` tokens per dispatch,
    active-masking slots that hit EOS / their token budget mid-chunk.
    One Python dispatch and one host transfer amortize over ``chunk``
    tokens — the mode to use when the queue is deep (slots freed
    mid-chunk only refill at the chunk boundary, so keep chunks short
    when requests are scarce).

Admission grouping: requests are admitted together when their prompts
share a shape bucket.  Attention-family models
(``Model.supports_padded_prefill()``) prefill ragged prompts right-padded
to a power-of-two length with exact per-row ``lens`` (causality plus the
decode-side ``kv_len`` mask make this bit-exact); recurrent / MoE /
encoder-decoder families group by exact prompt length instead (their
state or routing would absorb pad steps).

``engine="legacy"`` keeps the original per-slot host-sampling path as a
benchmark baseline (`benchmarks/serve_bench.py` asserts greedy token
parity between the two).

``engine="paged"`` swaps the dense per-slot ``(max_seq,)`` KV rectangles
for a global page pool (``models.api.Model.init_paged_cache``): K/V live
in ``(L, KH, num_pages, page, Dh)`` pools and each slot maps logical
pages to physical ones through a ``(max_batch, max_pages)`` page table.
HBM then scales with *live tokens*, not ``max_batch x max_seq``:

  * pages are allocated at admission for the request's full budget
    (``ceil((plen + max_new_tokens - 1) / page)`` — no mid-decode OOM)
    and freed at retirement through a host-side free list
    (:class:`PagePool`);
  * full prompt pages are deduplicated across requests by a chain hash
    of the token prefix they cover: two requests sharing a prompt prefix
    map the same physical pages (refcounted, read-only — decode only
    ever writes at ``pos >= plen``, past every shared page);
  * pool page 0 is reserved as a write-absorbing null page: retired
    slots keep decoding inside the static batch, so their table rows
    are parked at ``-1`` (clamped to page 0 by the attention update)
    and they can never corrupt live allocations;
  * admission is prompt-length-aware for every non-legacy engine: pass 0
    pulls all queued requests sharing the head-of-queue's shape bucket
    (bigger groups, fewer prefill dispatches), pass 1 fills the
    remaining slots FIFO — the head is always admitted first, so no
    request starves.

The decode hot loop is unchanged — ``decode_step`` dispatches on the
cache layout, so fused sampling and chunked decode run identically over
paged caches, and greedy tokens agree bit-for-bit with ``fused``.

Determinism: a slot's sample stream is keyed by ``fold_in(fold_in(seed,
slot), position)`` — reproducible run-to-run, and identical between
step-by-step and chunked decode for a given slot assignment (chunked
refill happens at chunk boundaries, so when requests outnumber slots a
request may land in a different slot and draw a different — but equally
deterministic — stream).  The legacy path instead consumes one global
split per sampled token, so temperature>0 draws differ between the
engines; greedy tokens agree bit-for-bit.

``spec_k > 0`` turns the chunked scan into **speculative draft/verify
rounds** (``repro.models.speculate``): per round, k drafts per slot —
from the free device-side n-gram/prompt-lookup proposer, or a smaller
same-vocab ``draft`` model — are scored by one ``Model.verify_step``
dispatch (a ``q_len = k+1`` decode-attention read) and the longest
target-agreeing prefix is kept.  Rollback is a ``pos`` rewind: rejected
rows stay as dead garbage above ``pos``, masked by ``kv_len`` and
overwritten next round; the paged allocator reserves ``spec_k`` extra
rows per slot at admission so a verify pass never writes past the
reservation.  Greedy output is bit-identical to non-speculative decode
and temperature output is exactly target-distributed (rejection
sampling) — see ``docs/serving.md`` for the proposer matrix.

Tracing: the fused and paged paths mark their phases with
``jax.profiler.TraceAnnotation`` host spans (``serve.step`` holding
``serve.admit`` > ``serve.prefill``, ``serve.upload``, ``serve.decode``,
``serve.readback``, ``serve.retire``), so a profile puts every gap of the
device under the engine phase that left it waiting.  Span arguments are
host integers and short strings, built only while a trace is being
recorded; a span adds no device work and no host-device synchronisation.
``docs/serving.md`` lists the spans and their arguments.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import pages_per_block
from repro.models import speculate
from repro.models.api import Model

Pytree = Any

_MIN_SEQ_BUCKET = 8

_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    extra: Optional[Dict[str, np.ndarray]] = None
    # host clock (``time.perf_counter``) at ``submit``: the queue wait
    # that the ``serve.admit`` span reports
    submitted: float = dataclasses.field(default=0.0, repr=False,
                                         compare=False)


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: List[int]
    prompt_len: int
    finished_reason: str  # eos | length


def _pow2_bucket(n: int, cap: int) -> int:
    """Smallest power of two >= n, clamped to cap (bounds jit retraces)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class PagePool:
    """Host-side allocator for the global K/V page pool.

    Page 0 is reserved as the null/parking page (never handed out):
    retired slots' table rows clamp to it, so a stale write can never
    land in a live allocation.  Full prompt pages are deduplicated by a
    *chain hash* — a digest of every prompt token the page and its
    predecessors cover — so identical prefixes map identical physical
    pages.  Sharing is sound because a causal model's K/V at position
    ``t`` depends only on tokens ``<= t``, and shared pages are
    read-only (decode writes start at ``pos >= plen``, past them).
    Registry entries are refcounted with the pages themselves and drop
    out when the last owner frees the page.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page 0 is the "
                             f"reserved null page), got {num_pages}")
        self.num_pages = num_pages
        self.page = page_size
        self.refs = np.zeros(num_pages, np.int32)
        self._free = list(range(num_pages - 1, 0, -1))  # stack: pop() -> 1 first
        self._registry: Dict[bytes, int] = {}   # chain hash -> physical page
        self._page_hash: Dict[int, bytes] = {}  # physical page -> chain hash
        self.prefix_hits = 0
        self.prefix_lookups = 0

    @property
    def capacity(self) -> int:
        """Allocatable pages (excludes the reserved null page)."""
        return self.num_pages - 1

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.capacity - len(self._free)

    @property
    def hit_rate(self) -> float:
        return self.prefix_hits / max(1, self.prefix_lookups)

    def lookup(self, chain_hash: bytes) -> Optional[int]:
        """Find a shared prompt page; increfs and returns it on a hit."""
        self.prefix_lookups += 1
        pid = self._registry.get(chain_hash)
        if pid is None:
            return None
        self.prefix_hits += 1
        self.refs[pid] += 1
        return pid

    def alloc(self, chain_hash: Optional[bytes] = None) -> Optional[int]:
        """Pop a free page (ref = 1), registering it for prefix sharing
        when a chain hash is given.  Returns None when the pool is dry."""
        if not self._free:
            return None
        pid = self._free.pop()
        self.refs[pid] = 1
        if chain_hash is not None:
            self._registry[chain_hash] = pid
            self._page_hash[pid] = chain_hash
        return pid

    def free(self, pid: int) -> None:
        """Decref; the page returns to the free list (and leaves the
        sharing registry) when its last owner lets go."""
        self.refs[pid] -= 1
        if self.refs[pid] == 0:
            h = self._page_hash.pop(pid, None)
            if h is not None:
                self._registry.pop(h, None)
            self._free.append(pid)


def _chain_hash(prompt: np.ndarray, end: int) -> bytes:
    """Digest of ``prompt[:end]`` — the sharing key for the page whose
    last covered position is ``end - 1``."""
    return hashlib.sha1(np.ascontiguousarray(
        prompt[:end], dtype=np.int32).tobytes()).digest()


def _cache_batch_axes(model: Model, max_seq: int) -> Pytree:
    """Per-leaf batch-axis index of the decode cache (-1 for leaves shared
    across slots), found by diffing cache specs at two batch sizes — no
    shape guessing at insert time, correct even for ``max_batch == 1``."""
    a = model.cache_specs(1, max_seq)
    b = model.cache_specs(2, max_seq)

    def one(x, y):
        if x.shape == y.shape:
            return -1
        return next(i for i, (p, q) in enumerate(zip(x.shape, y.shape))
                    if p != q)

    return jax.tree.map(one, a, b)


def _insert_rows(batched: Pytree, rows: Pytree, slots: jax.Array,
                 n_valid: jax.Array, axes: Pytree) -> Pytree:
    """Scatter the first ``n_valid`` rows of a prefilled cache into slots
    ``slots[:n_valid]`` of the batched cache.  ``slots`` and ``n_valid``
    are traced, so one compiled program serves every admission batch of
    the same bucket shape."""

    def one(b, g, ax):
        if ax < 0:
            return b  # shared (non-batched) leaf

        def body(i, acc):
            row = jax.lax.dynamic_slice_in_dim(g, i, 1, axis=ax)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, row.astype(acc.dtype), slots[i], axis=ax
            )

        return jax.lax.fori_loop(0, n_valid, body, b)

    return jax.tree.map(one, batched, rows, axes)


def _make_prefill_insert(model: Model, max_seq: int, axes: Pytree,
                         use_lens: bool):
    """Jittable batched admission: prefill a request group, sample each
    row's first token on device, and scatter the group cache into the
    engine's slots — one dispatch per admission group."""
    from repro.models import sampling

    def prefill_insert(params, batched_cache, tokens, extra, lens, slots,
                       n_valid, base_key, temps):
        logits, cache1 = model.prefill(
            params, tokens, extra, max_seq=max_seq,
            lens=lens if use_lens else None,
        )
        keys = sampling.slot_keys(base_key, slots, lens - 1)
        toks = sampling.sample_tokens(logits, keys, temps)
        new_cache = _insert_rows(batched_cache, cache1, slots, n_valid, axes)
        return toks, new_cache

    return prefill_insert


def _make_paged_prefill_insert(model: Model, page: int, use_lens: bool):
    """Jittable batched admission for the paged cache: prefill a request
    group densely (a throwaway ``(n_pad, S)`` mini-cache), sample each
    row's first token on device, then scatter the prompt K/V into the
    global pool one page at a time.

    The copy list (``src_row``/``src_page`` -> ``dst_page``) is built on
    the host from the admission plan: shared prefix pages already hold
    their data and are simply skipped, so a full prefix hit costs zero
    page copies.  ``n_copy``/``n_valid`` are traced (bounded by the
    pow-of-two padding of the arrays), so one compiled program serves
    every admission batch of the same bucket shape."""
    from repro.models import sampling

    def paged_prefill_insert(params, k_pool, v_pool, pos, tokens, extra,
                             lens, slots, n_valid, src_row, src_page,
                             dst_page, n_copy, base_key, temps):
        # the mini-cache is padded to a page multiple so every prompt
        # page slices in bounds (pad K/V is garbage but masked by kv_len
        # until decode overwrites it, exactly like the dense engine)
        s_cache = -(-tokens.shape[1] // page) * page
        logits, cache1 = model.prefill(
            params, tokens, extra, max_seq=s_cache,
            lens=lens if use_lens else None,
        )
        keys = sampling.slot_keys(base_key, slots, lens - 1)
        toks = sampling.sample_tokens(logits, keys, temps)
        kd, vd = cache1["k"], cache1["v"]  # (L, n_pad, s_cache, KH, Dh)
        L, _, _, KH, Dh = kd.shape

        def copy(i, pools):
            kp, vp = pools
            r, lp, dp = src_row[i], src_page[i], dst_page[i]
            blk_k = jax.lax.dynamic_slice(
                kd, (0, r, lp * page, 0, 0), (L, 1, page, KH, Dh))
            blk_v = jax.lax.dynamic_slice(
                vd, (0, r, lp * page, 0, 0), (L, 1, page, KH, Dh))
            # (L, page, KH, Dh) -> pool block (L, KH, 1, page, Dh)
            blk_k = blk_k[:, 0].transpose(0, 2, 1, 3)[:, :, None]
            blk_v = blk_v[:, 0].transpose(0, 2, 1, 3)[:, :, None]
            kp = jax.lax.dynamic_update_slice(
                kp, blk_k.astype(kp.dtype), (0, 0, dp, 0, 0))
            vp = jax.lax.dynamic_update_slice(
                vp, blk_v.astype(vp.dtype), (0, 0, dp, 0, 0))
            return kp, vp

        k_pool, v_pool = jax.lax.fori_loop(0, n_copy, copy, (k_pool, v_pool))
        pos = jax.lax.fori_loop(
            0, n_valid, lambda i, p: p.at[slots[i]].set(lens[i]), pos)
        return toks, k_pool, v_pool, pos

    return paged_prefill_insert


def _make_decode_chunk(model: Model, steps: int):
    """Jittable chunked decode: ``steps`` fused decode+sample iterations
    under ``lax.scan``, masking slots that finish (EOS or budget) so
    their later tokens are dead.  Emits ``(steps, B)`` tokens — the
    chunk's single host transfer."""

    def decode_chunk(params, cache, last_token, base_key, temps, active,
                     counts, budgets, eos_id, greedy_only=False):
        def body(carry, _):
            cache, last, act, cnt = carry
            toks, cache = model.decode_and_sample(
                params, cache, last[:, None], base_key, temps,
                greedy_only=greedy_only,
            )
            cnt = cnt + act.astype(jnp.int32)
            emit = jnp.where(act, toks, jnp.zeros_like(toks))
            finished = act & ((toks == eos_id) | (cnt >= budgets))
            last = jnp.where(act, toks, last)
            return (cache, last, act & ~finished, cnt), emit

        (cache, _, _, _), seq = jax.lax.scan(
            body, (cache, last_token, active, counts), None, length=steps
        )
        return seq, cache

    return decode_chunk


def _make_spec_chunk(model: Model, spec_k: int, rounds: int, ngram_n: int,
                     draft: Optional[Model] = None):
    """Jittable speculative decode chunk: ``rounds`` draft/verify rounds
    under ``lax.scan``, each emitting 1..k+1 tokens per slot from ONE
    target dispatch (:meth:`repro.models.api.Model.verify_step`).

    Per round and slot: propose ``k`` drafts (device n-gram lookup over
    the slot's own history, or ``k`` draft-model decode steps), verify
    all ``k+1`` positions at once, keep the longest target-agreeing
    prefix (exact-match for greedy slots, rejection sampling for
    temperature slots — lossless either way, see
    :mod:`repro.models.speculate`), then gate the surviving run on EOS /
    token budget exactly like :func:`_make_decode_chunk` and rewind the
    cache ``pos`` to the last committed token.  Rejected rows need no
    K/V surgery — ``kv_len`` masking hides everything above ``pos``.

    Emits ``(rounds, B, k+3)`` int32 — per round the ``k+1`` candidate
    emissions plus ``m`` (tokens committed) and ``accepted`` (drafts
    survived) columns — the chunk's single host transfer."""
    K = spec_k

    def spec_chunk(params, cache, draft_params, draft_cache, last_token, hist,
                   base_key, temps, active, counts, budgets, eos_id,
                   greedy_only=False):
        B = last_token.shape[0]
        slots = jnp.arange(B)

        def body(carry, _):
            cache, dcache, last, hist, act, cnt = carry
            pos = cache["pos"]  # (B,) == plen + cnt - 1 for live slots

            if draft is None:
                drafts = speculate.ngram_propose(
                    hist, pos + 1, k=K, n=ngram_n)
                q_probs = None
                dcache2 = dcache
            else:
                safe = jnp.where(temps > 0, temps, 1.0)

                def dstep(c, j):
                    dc, cur = c
                    lg, dc = draft.decode_step(draft_params, dc, cur[:, None])
                    lg32 = lg.astype(jnp.float32) / safe[:, None]
                    keys = speculate.spec_keys(
                        base_key, slots, pos + 1 + j, speculate.TAG_DRAFT)
                    samp = jax.vmap(jax.random.categorical)(keys, lg32)
                    tok = jnp.where(temps > 0, samp,
                                    jnp.argmax(lg32, -1)).astype(jnp.int32)
                    return (dc, tok), (tok, jax.nn.softmax(lg32, axis=-1))

                (dcache2, _), (dt_, qt_) = jax.lax.scan(
                    dstep, (dcache, last), jnp.arange(K))
                drafts = dt_.T                      # (B, K)
                q_probs = qt_.transpose(1, 0, 2)    # (B, K, V)

            vt = jnp.concatenate([last[:, None], drafts], axis=1)  # (B, K+1)
            logits, cache2 = model.verify_step(params, cache, vt)
            emitted, m, accepted = speculate.accept_and_emit(
                logits, drafts, q_probs, temps, base_key, slots, pos + 1,
                bonus=(draft is None), greedy_only=greedy_only,
            )
            # gate the run on EOS and remaining budget, like the plain
            # chunk's per-step mask — tokens after the first EOS or past
            # the budget are dead
            jcol = jnp.arange(K + 1)[None]
            is_eos = (jcol < m[:, None]) & (emitted == eos_id)
            eos_idx = jnp.min(jnp.where(is_eos, jcol, K + 2), axis=1)
            m_eff = jnp.minimum(jnp.minimum(m, eos_idx + 1),
                                jnp.maximum(budgets - cnt, 0))
            m_eff = jnp.where(act, m_eff, 0)

            new_pos = pos + m_eff  # rollback: rejected rows stay above pos
            cache2 = dict(cache2, pos=new_pos)
            if draft is not None:
                # the draft cache holds K/V for [last, d_1..d_{k-1}] at
                # pos..pos+k-1; every committed token <= the accepted
                # prefix matches it, so syncing pos is the whole rollback
                dcache2 = dict(dcache2, pos=new_pos)
            cnt2 = cnt + m_eff
            lidx = jnp.clip(m_eff - 1, 0, K)
            last2 = jnp.where(
                act & (m_eff > 0),
                jnp.take_along_axis(emitted, lidx[:, None], axis=1)[:, 0],
                last)
            fin = act & ((eos_idx + 1 <= m_eff) | (cnt2 >= budgets))
            hist2 = speculate.update_history(hist, pos, emitted, m_eff, act)
            out = jnp.concatenate(
                [emitted, m_eff[:, None], accepted[:, None]], axis=1)
            return (cache2, dcache2, last2, hist2, act & ~fin, cnt2), out

        (cache, dcache, _, hist, _, _), rows = jax.lax.scan(
            body, (cache, draft_cache, last_token, hist, active, counts),
            None, length=rounds)
        return rows, cache, dcache, hist

    return spec_chunk


class ServeEngine:
    def __init__(self, model: Model, params: Pytree, *, max_batch: int = 8,
                 max_seq: int = 256, eos_id: int = 2, seed: int = 0,
                 engine: str = "fused", decode_chunk: int = 1,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 spec_k: int = 0, spec_ngram_n: int = 3,
                 draft: Optional[Model] = None,
                 draft_params: Optional[Pytree] = None):
        if engine not in ("fused", "legacy", "paged"):
            raise ValueError(f"engine must be 'fused', 'legacy' or 'paged', "
                             f"got {engine!r}")
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
        if engine == "legacy" and decode_chunk > 1:
            raise ValueError("decode_chunk > 1 requires the fused engine: "
                             "the legacy baseline decodes token-by-token")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k == 0 and draft is not None:
            raise ValueError("a draft model requires spec_k >= 1")
        if spec_k > 0:
            if engine == "legacy":
                raise ValueError("speculative decoding (spec_k > 0) requires "
                                 "the fused or paged engine")
            if not model.supports_speculative():
                raise ValueError(
                    f"speculative decoding unsupported for family "
                    f"{model.cfg.family!r}: the decode cache cannot roll "
                    f"back rejected drafts")
            if spec_ngram_n < 1:
                raise ValueError(f"spec_ngram_n must be >= 1, "
                                 f"got {spec_ngram_n}")
            if draft is not None:
                if draft_params is None:
                    raise ValueError("a draft model requires draft_params")
                if draft.cfg.vocab_size != model.cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab ({draft.cfg.vocab_size}) must match "
                        f"target vocab ({model.cfg.vocab_size}): drafts are "
                        f"target token ids")
                if not draft.supports_speculative():
                    raise ValueError(
                        f"draft family {draft.cfg.family!r} cannot draft: "
                        f"its cache cannot roll back rejected drafts")
                if (model.supports_padded_prefill()
                        and not draft.supports_padded_prefill()):
                    raise ValueError(
                        "draft model must support padded prefill when the "
                        "target does: both prefill the same admission "
                        "groups")
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.engine = engine
        self.decode_chunk = decode_chunk
        self.rng = jax.random.PRNGKey(seed)      # legacy serial sampling
        self.base_key = jax.random.PRNGKey(seed)  # fused per-slot fold-in

        self.pool: Optional[PagePool] = None
        if engine == "paged":
            if not model.supports_paged_cache():
                raise ValueError(
                    f"engine='paged' requires a dense attention decode "
                    f"cache; family {model.cfg.family!r} "
                    f"(encdec={model.cfg.is_encoder_decoder}) keeps "
                    f"recurrent state that cannot be paged"
                )
            if page_size < 1 or page_size & (page_size - 1):
                raise ValueError(f"page_size must be a power of two, "
                                 f"got {page_size}")
            self.page_size = page_size
            self._max_pages = -(-max_seq // page_size)  # table width / slot
            if num_pages is None:
                # full-occupancy capacity + the reserved null page; pass a
                # smaller pool to make HBM proportional to live tokens
                num_pages = 1 + max_batch * self._max_pages
            self.num_pages = num_pages
            self.pool = PagePool(num_pages, page_size)
            self.cache = model.init_paged_cache(
                max_batch, num_pages=num_pages, page_size=page_size,
                max_pages=self._max_pages)
            # host mirror of the device page table; synced before decode
            self._ptable = np.full((max_batch, self._max_pages), -1, np.int32)
            self._ptable_dirty = False
            self._slot_pages: List[List[int]] = [[] for _ in range(max_batch)]
        else:
            self.cache = model.init_cache(max_batch, max_seq)
        self.active = np.zeros(max_batch, dtype=bool)
        self.req: List[Optional[Request]] = [None] * max_batch
        self.emitted: List[List[int]] = [[] for _ in range(max_batch)]
        self.last_token = np.zeros(max_batch, dtype=np.int32)
        self.temps = np.zeros(max_batch, dtype=np.float32)
        self.queue: Deque[Request] = deque()
        self.done: List[Completion] = []
        # instrumentation: fast-path D2H transfers (count, elements) and
        # chunk utilization (scanned decode steps actually consumed vs
        # dispatched — low utilization means chunks outlive the work)
        self.d2h_transfers = 0
        self.d2h_elems = 0
        self.chunk_steps_total = 0
        self.chunk_steps_used = 0
        # speculative decoding counters (spec_k > 0)
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_tokens = 0

        self._padded_admission = model.supports_padded_prefill()
        self._axes = _cache_batch_axes(model, max_seq)

        self._decode = jax.jit(model.decode_step)
        self._decode_sample = jax.jit(model.decode_and_sample,
                                      static_argnames=("greedy_only",))
        self._prefill = jax.jit(
            lambda p, t, e: model.prefill(p, t, e, max_seq=max_seq)
        )
        # slot writer: slot index is traced, so admissions never retrace
        self._insert = jax.jit(
            lambda batched, single, slot: _insert_rows(
                batched, single, slot[None], jnp.int32(1), self._axes
            )
        )
        self._prefill_insert_exact = jax.jit(
            _make_prefill_insert(model, max_seq, self._axes, use_lens=False)
        )
        self._prefill_insert_pad = jax.jit(
            _make_prefill_insert(model, max_seq, self._axes, use_lens=True)
        )
        if engine == "paged":
            self._paged_insert_exact = jax.jit(
                _make_paged_prefill_insert(model, page_size, use_lens=False)
            )
            self._paged_insert_pad = jax.jit(
                _make_paged_prefill_insert(model, page_size, use_lens=True)
            )
        self._decode_chunk = (
            jax.jit(_make_decode_chunk(model, decode_chunk),
                    static_argnames=("greedy_only",))
            if engine in ("fused", "paged") and decode_chunk > 1 else None
        )

        self.spec_k = spec_k
        self.spec_ngram_n = spec_ngram_n
        self.draft = draft
        self.draft_params = draft_params
        self._spec_chunk = None
        if spec_k > 0:
            # history buffer (n-gram proposer source + committed-token
            # record): covers every reachable position of the engine
            cap = (self._max_pages * page_size if engine == "paged"
                   else max_seq)
            self._hist_cap = cap
            self.hist = jnp.zeros((max_batch, cap), jnp.int32)
            self._hist_dirty: List[int] = []
            self._spec_chunk = jax.jit(
                _make_spec_chunk(model, spec_k, max(1, decode_chunk),
                                 spec_ngram_n, draft),
                static_argnames=("greedy_only",))
            if draft is not None:
                # the draft serves from its own dense fused cache sized
                # to the target's reachable positions, admitted alongside
                # the target (its admission-sampled tokens are discarded)
                self._draft_cache = draft.init_cache(max_batch, cap)
                d_axes = _cache_batch_axes(draft, cap)
                self._draft_insert_exact = jax.jit(
                    _make_prefill_insert(draft, cap, d_axes, use_lens=False))
                self._draft_insert_pad = jax.jit(
                    _make_prefill_insert(draft, cap, d_axes, use_lens=True))
            else:
                self._draft_cache = jnp.zeros((0,), jnp.float32)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request.  Validation happens here — once a request is
        accepted, admission/decode cannot fail or silently clamp, so a
        queued request is never dropped or corrupted mid-batch."""
        plen = len(req.prompt)
        if plen < 1:
            raise ValueError("prompt must have at least one token")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {req.max_new_tokens}"
            )
        # worst case the request decodes its full budget: the last decode
        # writes K/V at position plen + max_new_tokens - 2, which must
        # stay inside the cache or the scatter silently clamps/drops.
        # Speculation widens the margin by spec_k: a verify pass entered
        # one token before the budget still writes k draft rows past it
        if self.engine == "paged":
            need = -(-(plen + req.max_new_tokens - 1 + self.spec_k)
                     // self.page_size)
            limit = min(self.pool.capacity, self._max_pages)
            if need > limit:
                raise ValueError(
                    f"prompt ({plen}) + max_new_tokens "
                    f"({req.max_new_tokens})"
                    + (f" + spec_k ({self.spec_k})" if self.spec_k else "")
                    + f" needs {need} KV pages but "
                    f"engine='paged' can map at most {limit} pages per "
                    f"request ({self.pool.capacity} allocatable pages of "
                    f"page_size={self.page_size} in the pool, "
                    f"{self._max_pages} page-table entries per slot): "
                    f"the request could never be admitted"
                )
        elif plen + req.max_new_tokens - 1 + self.spec_k > self.max_seq:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({req.max_new_tokens}) "
                f"- 1"
                + (f" + spec_k ({self.spec_k})" if self.spec_k else "")
                + f" exceeds max_seq={self.max_seq}: the decode would "
                f"overflow the KV cache"
            )
        req.submitted = time.perf_counter()
        self.queue.append(req)

    def _to_host(self, arr: jax.Array) -> np.ndarray:
        out = np.asarray(arr)
        self.d2h_transfers += 1
        self.d2h_elems += out.size
        return out

    def _all_greedy(self) -> bool:
        """Static sampling hint: True when no active slot needs the
        categorical draw (at most two jit variants exist per shape)."""
        return not bool((self.temps[self.active] > 0).any())

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    @staticmethod
    def _extra_sig(extra: Optional[Dict[str, np.ndarray]]):
        if not extra:
            return None
        return tuple(sorted(
            (k, tuple(np.asarray(v).shape), np.asarray(v).dtype.str)
            for k, v in extra.items()
        ))

    def _group_key(self, req: Request) -> Tuple:
        """Admission-group key: requests sharing it prefill in one jitted
        dispatch.  Paged groups bucket to at least one page so the
        page-granular scatter slices in bounds."""
        plen = len(req.prompt)
        sig = self._extra_sig(req.extra)
        if self._padded_admission:
            seq = _pow2_bucket(max(plen, _MIN_SEQ_BUCKET), self.max_seq)
            if self.engine == "paged":
                seq = max(seq, self.page_size)
            return ("pad", seq, sig)
        return ("exact", plen, sig)

    def _select(self, n_slots: int) -> List[Request]:
        """Prompt-length-aware two-pass selection: pass 0 pulls every
        queued request sharing the head request's shape bucket forward
        (bigger admission groups, fewer prefill dispatches); pass 1
        fills the remaining slots FIFO.  The head of the queue is always
        selected first, so reordering never starves a request."""
        if not self.queue or n_slots <= 0:
            return []
        head_key = self._group_key(self.queue[0])
        picked: List[Request] = []
        rest: List[Request] = []
        for r in self.queue:
            if len(picked) < n_slots and self._group_key(r) == head_key:
                picked.append(r)
            else:
                rest.append(r)
        while rest and len(picked) < n_slots:
            picked.append(rest.pop(0))
        self.queue = deque(rest)
        return picked

    def _admit(self) -> None:
        """Admit queued requests: on the fused and paged paths inside a
        ``serve.admit`` span whose arguments describe what was admitted."""
        if self.engine == "legacy":
            self._admit_legacy()
            return
        with _span("serve.admit") as sp:
            t = time.perf_counter()
            admitted = (self._admit_paged() if self.engine == "paged"
                        else self._admit_fused())
            if sp.is_enabled():
                sp.set_metadata(
                    admitted=len(admitted),
                    prompt_tokens=sum(len(r.prompt) for r in admitted),
                    uids=" ".join(str(r.uid) for r in admitted),
                    queue_wait_ms=max((1e3 * (t - r.submitted)
                                       for r in admitted), default=0.0))

    def _admit_fused(self) -> List[Request]:
        if not self.queue:
            return []
        free = np.flatnonzero(~self.active)
        if free.size == 0:
            return []
        selected = self._select(int(free.size))
        pairs = [(int(free[i]), req) for i, req in enumerate(selected)]
        groups: Dict[Tuple, List[Tuple[int, Request]]] = {}
        for slot, req in pairs:
            groups.setdefault(self._group_key(req), []).append((slot, req))
        for (kind, seq_len, _), members in groups.items():
            self._admit_group(kind, seq_len, members)
        return selected

    def _admit_group(self, kind: str, seq_len: int,
                     members: List[Tuple[int, Request]]) -> None:
        n = len(members)
        n_pad = _pow2_bucket(n, self.max_batch)
        tokens = np.zeros((n_pad, seq_len), np.int32)
        lens = np.ones(n_pad, np.int32)
        temps = np.zeros(n_pad, np.float32)
        slots = np.zeros(n_pad, np.int32)
        for i, (slot, req) in enumerate(members):
            plen = len(req.prompt)
            tokens[i, :plen] = np.asarray(req.prompt, np.int32)
            lens[i] = plen
            temps[i] = req.temperature
            slots[i] = slot
        extra = None
        if members[0][1].extra:
            extra = {}
            for k in sorted(members[0][1].extra):
                rows = [np.asarray(req.extra[k]) for _, req in members]
                rows += [rows[0]] * (n_pad - n)
                extra[k] = jnp.asarray(np.stack(rows))
        fn = (self._prefill_insert_pad if kind == "pad"
              else self._prefill_insert_exact)
        with _span("serve.prefill") as sp:
            if sp.is_enabled():
                sp.set_metadata(rows=n, bucket=seq_len,
                                prompt_tokens=int(lens[:n].sum()))
            first, self.cache = fn(
                self.params, self.cache, jnp.asarray(tokens), extra,
                jnp.asarray(lens), jnp.asarray(slots), jnp.int32(n),
                self.base_key, jnp.asarray(temps),
            )
            self._admit_draft(kind, tokens, lens, slots, temps, n)
            first = np.asarray(first)
        for i, (slot, req) in enumerate(members):
            self._place(slot, req, int(first[i]))

    def _admit_draft(self, kind: str, tokens, lens, slots, temps,
                     n: int) -> None:
        """Prefill the draft model's cache for a freshly admitted group
        (same rows, same slots).  The draft's admission-sampled tokens
        are discarded — the target's prefill decides the first token —
        and its cache position lands at ``lens``, in lockstep with the
        target."""
        if self.spec_k == 0 or self.draft is None:
            return
        dfn = (self._draft_insert_pad if kind == "pad"
               else self._draft_insert_exact)
        _, self._draft_cache = dfn(
            self.draft_params, self._draft_cache, jnp.asarray(tokens), None,
            jnp.asarray(lens), jnp.asarray(slots), jnp.int32(n),
            self.base_key, jnp.asarray(temps),
        )

    # ---- paged admission ---------------------------------------------
    def _plan_pages(self, req: Request):
        """Reserve the request's full page budget (prompt + decode room,
        so decode can never OOM), sharing full prompt pages through the
        chain-hash registry.  Returns ``(pages, copy_lps)`` — physical
        pages per logical page, plus which logical pages need their K/V
        copied from the prefill (shared hits need none) — or None with
        every reservation rolled back when the pool can't fit it."""
        plen = len(req.prompt)
        # + spec_k: room for the draft rows a final verify pass writes
        # past the budget (over-reserved tail pages free at retirement)
        n_total = -(-(plen + req.max_new_tokens - 1 + self.spec_k)
                    // self.page_size)
        n_prompt = -(-plen // self.page_size)
        n_full = plen // self.page_size  # only fully-covered pages share
        prompt = np.asarray(req.prompt, np.int32)
        pages: List[int] = []
        copies: List[int] = []
        for k in range(n_total):
            h = None
            pid = None
            if k < n_full:
                h = _chain_hash(prompt, (k + 1) * self.page_size)
                pid = self.pool.lookup(h)
            if pid is None:
                pid = self.pool.alloc(h)
                if pid is None:
                    for p in pages:
                        self.pool.free(p)
                    return None
                if k < n_prompt:
                    copies.append(k)
            pages.append(pid)
        return pages, copies

    def _admit_paged(self) -> List[Request]:
        if not self.queue:
            return []
        free = np.flatnonzero(~self.active)
        if free.size == 0:
            return []
        selected = self._select(int(free.size))
        admitted: List[Tuple[int, Request, List[int], List[int]]] = []
        for i, req in enumerate(selected):
            plan = self._plan_pages(req)
            if plan is None:
                # pool exhausted: requeue this and everything behind it
                # at the front, order preserved — retirements will free
                # pages and the next admission retries
                self.queue.extendleft(reversed(selected[i:]))
                break
            admitted.append((int(free[len(admitted)]), req, *plan))
        if not admitted:
            return []
        groups: Dict[Tuple, List[Tuple[int, Request, List[int], List[int]]]] = {}
        for entry in admitted:
            groups.setdefault(self._group_key(entry[1]), []).append(entry)
        for (kind, seq_len, _), members in groups.items():
            self._admit_group_paged(kind, seq_len, members)
        return [entry[1] for entry in admitted]

    def _admit_group_paged(self, kind: str, seq_len: int, members) -> None:
        n = len(members)
        n_pad = _pow2_bucket(n, self.max_batch)
        tokens = np.zeros((n_pad, seq_len), np.int32)
        lens = np.ones(n_pad, np.int32)
        temps = np.zeros(n_pad, np.float32)
        slots = np.zeros(n_pad, np.int32)
        src_row: List[int] = []
        src_page: List[int] = []
        dst_page: List[int] = []
        for i, (slot, req, pages, copies) in enumerate(members):
            plen = len(req.prompt)
            tokens[i, :plen] = np.asarray(req.prompt, np.int32)
            lens[i] = plen
            temps[i] = req.temperature
            slots[i] = slot
            row = np.full(self._max_pages, -1, np.int32)
            row[:len(pages)] = pages
            self._ptable[slot] = row
            self._slot_pages[slot] = pages
            for lp in copies:
                src_row.append(i)
                src_page.append(lp)
                dst_page.append(pages[lp])
        self._ptable_dirty = True
        n_copy = len(src_row)
        c_pad = _pow2_bucket(max(n_copy, 1), 1 << 30)
        sr = np.zeros(c_pad, np.int32)
        sp = np.zeros(c_pad, np.int32)
        dp = np.zeros(c_pad, np.int32)
        sr[:n_copy] = src_row
        sp[:n_copy] = src_page
        dp[:n_copy] = dst_page
        extra = None
        if members[0][1].extra:
            extra = {}
            for k in sorted(members[0][1].extra):
                rows = [np.asarray(req.extra[k]) for _, req, _, _ in members]
                rows += [rows[0]] * (n_pad - n)
                extra[k] = jnp.asarray(np.stack(rows))
        fn = (self._paged_insert_pad if kind == "pad"
              else self._paged_insert_exact)
        with _span("serve.prefill") as span:
            if span.is_enabled():
                span.set_metadata(rows=n, bucket=seq_len,
                                  prompt_tokens=int(lens[:n].sum()))
            toks, nk, nv, npos = fn(
                self.params, self.cache["k_pool"], self.cache["v_pool"],
                self.cache["pos"], jnp.asarray(tokens), extra,
                jnp.asarray(lens), jnp.asarray(slots), jnp.int32(n),
                jnp.asarray(sr), jnp.asarray(sp), jnp.asarray(dp),
                jnp.int32(n_copy), self.base_key, jnp.asarray(temps),
            )
            self.cache = {"k_pool": nk, "v_pool": nv,
                          "page_table": self.cache["page_table"],
                          "pos": npos}
            self._admit_draft(kind, tokens, lens, slots, temps, n)
            first = np.asarray(toks)
        for i, (slot, req, _, _) in enumerate(members):
            self._place(slot, req, int(first[i]))

    def _sync_hist(self) -> None:
        """Upload history rows for freshly admitted slots (prompt + the
        admission-sampled token).  Device-side rounds keep continuing
        slots' rows current, so only new admissions transfer."""
        if self.spec_k == 0 or not self._hist_dirty:
            return
        idx = sorted(set(self._hist_dirty))
        self._hist_dirty = []
        rows = np.zeros((len(idx), self._hist_cap), np.int32)
        for r, slot in enumerate(idx):
            req = self.req[slot]
            if req is None:  # admitted and instantly retired: row is dead
                continue
            seq = np.concatenate([np.asarray(req.prompt, np.int64),
                                  np.asarray(self.emitted[slot], np.int64)])
            seq = seq[: self._hist_cap]
            rows[r, : len(seq)] = seq
        self.hist = self.hist.at[jnp.asarray(np.asarray(idx, np.int32))].set(
            jnp.asarray(rows))

    def _sync_ptable(self) -> None:
        """Upload the host page-table mirror before a decode dispatch.
        Rows parked at -1 (retired slots) clamp to the null page, so a
        freed-and-reallocated page can never be written by its old
        owner."""
        if self.engine == "paged" and self._ptable_dirty:
            self.cache["page_table"] = jnp.asarray(self._ptable)
            self._ptable_dirty = False

    def _admit_legacy(self) -> None:
        while self.queue and not self.active.all():
            slot = int(np.argmax(~self.active))
            req = self.queue.popleft()
            tokens = jnp.asarray(req.prompt, jnp.int32)[None]
            extra = (
                {k: jnp.asarray(v)[None] for k, v in req.extra.items()}
                if req.extra else None
            )
            logits, cache1 = self._prefill(self.params, tokens, extra)
            self.cache = self._insert(self.cache, cache1, jnp.int32(slot))
            first = self._sample(logits[0], req.temperature)
            self._place(slot, req, int(first))

    def _place(self, slot: int, req: Request, first: int) -> None:
        """Occupy a slot with a freshly prefilled request and apply the
        retire rules to its admission-sampled token — a prefill-EOS (or a
        1-token budget) finishes the request without a decode step."""
        self.active[slot] = True
        self.req[slot] = req
        self.emitted[slot] = [first]
        self.last_token[slot] = first
        self.temps[slot] = req.temperature
        if self.spec_k > 0:
            self._hist_dirty.append(slot)
        if first == self.eos_id:
            self._retire(slot, "eos")
        elif req.max_new_tokens <= 1:
            self._retire(slot, "length")

    def _sample(self, logits: jax.Array, temperature: float) -> int:
        if temperature <= 0:
            return int(jnp.argmax(logits))
        self.rng, sub = jax.random.split(self.rng)
        return int(jax.random.categorical(sub, logits / temperature))

    def _retire(self, slot: int, reason: str) -> None:
        req = self.req[slot]
        self.done.append(
            Completion(req.uid, list(self.emitted[slot]), len(req.prompt), reason)
        )
        self.active[slot] = False
        self.req[slot] = None
        self.emitted[slot] = []
        if self.engine == "paged":
            for p in self._slot_pages[slot]:
                self.pool.free(p)
            self._slot_pages[slot] = []
            self._ptable[slot] = -1  # park: dead writes go to the null page
            self._ptable_dirty = True

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _consume(self, tok_rows: np.ndarray) -> None:
        """Apply decoded tokens, one (B,) row per decode step, to the host
        bookkeeping — the same retire rules the device chunk mask uses,
        so host and device state stay in lockstep."""
        self.chunk_steps_total += len(tok_rows)
        for row in tok_rows:
            if not self.active.any():
                break  # early-out: the rest of the chunk is dead work
            self.chunk_steps_used += 1
            for slot in range(self.max_batch):
                if not self.active[slot]:
                    continue
                req = self.req[slot]
                tok = int(row[slot])
                self.emitted[slot].append(tok)
                self.last_token[slot] = tok
                if tok == self.eos_id:
                    self._retire(slot, "eos")
                elif len(self.emitted[slot]) >= req.max_new_tokens:
                    self._retire(slot, "length")

    def _decode_args(self, steps: int) -> Dict[str, int]:
        """``serve.decode`` arguments: active rows, rows computed, live
        K/V tokens of the active rows, and the K/V positions the step
        spans over every computed row (page-table width x page, or
        ``max_seq``).  A paged engine adds ``kv_pages_walked``: the
        table pages in the blocks the paged kernel walks for the active
        rows, each row's ``ceil(kv_len / page)`` rounded up to its block
        of pages (``kv_len`` counts the ``spec_k`` draft rows a verify
        reads)."""
        width = (self._max_pages * self.page_size if self.engine == "paged"
                 else self.max_seq)
        args = {"rows": int(self.active.sum()), "batch": self.max_batch,
                "kv_tokens": self.live_tokens,
                "kv_capacity": self.max_batch * width, "steps": steps}
        if self.engine == "paged":
            ppb = pages_per_block(self.page_size, self._max_pages)
            bk = ppb * self.page_size
            args["kv_pages_walked"] = sum(
                ppb * -(-min(len(self.req[s].prompt) + len(self.emitted[s])
                             + self.spec_k, width) // bk)
                for s in range(self.max_batch) if self.active[s])
        return args

    def _collect(self, out: jax.Array, consume) -> None:
        """Read a decode dispatch's tokens back (``serve.readback``) and
        apply them with ``consume`` (``serve.retire``)."""
        with _span("serve.readback"):
            rows = self._to_host(out)
        with _span("serve.retire") as sp:
            n0 = len(self.done)
            consume(rows)
            if sp.is_enabled():
                done = self.done[n0:]
                sp.set_metadata(finished=len(done),
                                uids=" ".join(str(c.uid) for c in done))

    def step(self) -> None:
        """One engine iteration: admit new work, decode one token for every
        active slot, retire finished slots.  On the fused path this is one
        device dispatch and one (B,) host transfer."""
        if self.engine == "legacy":
            self._step_legacy()
            return
        with _span("serve.step", queued=len(self.queue)):
            self._admit()
            with _span("serve.upload"):
                self._sync_ptable()
            if not self.active.any():
                return
            with _span("serve.decode") as sp:
                if sp.is_enabled():
                    sp.set_metadata(**self._decode_args(1))
                toks, self.cache = self._decode_sample(
                    self.params, self.cache,
                    jnp.asarray(self.last_token)[:, None],
                    self.base_key, jnp.asarray(self.temps),
                    greedy_only=self._all_greedy(),
                )
            self._collect(toks, lambda row: self._consume(row[None]))

    def _step_legacy(self) -> None:
        self._admit_legacy()
        if not self.active.any():
            return
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(self.last_token)[:, None]
        )
        # full (B, V) host copy — the cost the fused path removes;
        # routed through _to_host so the instrumentation tells the truth
        logits = self._to_host(logits).astype(np.float32)
        row = np.zeros(self.max_batch, np.int32)
        for slot in range(self.max_batch):  # one dispatch per slot
            if not self.active[slot]:
                continue
            row[slot] = self._sample(jnp.asarray(logits[slot]),
                                     self.req[slot].temperature)
        self._consume(row[None])

    def step_chunk(self) -> int:
        """One chunked iteration: admit, then decode ``decode_chunk``
        tokens per slot in a single scanned dispatch.  Returns the number
        of decode steps executed (0 when idle)."""
        if self._decode_chunk is None:
            self.step()
            return 1
        with _span("serve.step", queued=len(self.queue)):
            self._admit()
            with _span("serve.upload"):
                self._sync_ptable()
            if not self.active.any():
                return 0
            budgets = np.asarray(
                [r.max_new_tokens if r is not None else 0 for r in self.req],
                np.int32,
            )
            counts = np.asarray([len(e) for e in self.emitted], np.int32)
            with _span("serve.decode") as sp:
                if sp.is_enabled():
                    sp.set_metadata(**self._decode_args(self.decode_chunk))
                seq, self.cache = self._decode_chunk(
                    self.params, self.cache, jnp.asarray(self.last_token),
                    self.base_key, jnp.asarray(self.temps),
                    jnp.asarray(self.active), jnp.asarray(counts),
                    jnp.asarray(budgets), jnp.int32(self.eos_id),
                    greedy_only=self._all_greedy(),
                )
            self._collect(seq, self._consume)
            return self.decode_chunk

    # ---- speculative decode ------------------------------------------
    def _consume_spec(self, rows: np.ndarray) -> None:
        """Apply speculative rounds — ``rows`` is ``(R, B, k+3)``: the
        round's candidate emissions plus its ``m`` (committed count) and
        ``accepted`` (surviving drafts) columns — with the same retire
        rules the device round mask uses, so host and device stay in
        lockstep."""
        mcol, acol = self.spec_k + 1, self.spec_k + 2
        self.chunk_steps_total += len(rows)
        for row in rows:
            if not self.active.any():
                break  # early-out: the rest of the chunk is dead work
            self.chunk_steps_used += 1
            for slot in range(self.max_batch):
                if not self.active[slot]:
                    continue
                req = self.req[slot]
                m = int(row[slot, mcol])
                self.spec_rounds += 1
                self.spec_proposed += self.spec_k
                self.spec_accepted += int(row[slot, acol])
                self.spec_tokens += m
                for j in range(m):
                    tok = int(row[slot, j])
                    self.emitted[slot].append(tok)
                    self.last_token[slot] = tok
                    if tok == self.eos_id:
                        self._retire(slot, "eos")
                        break
                    if len(self.emitted[slot]) >= req.max_new_tokens:
                        self._retire(slot, "length")
                        break

    def step_spec(self) -> int:
        """One speculative iteration: admit, then run ``decode_chunk``
        draft/verify rounds in a single scanned dispatch — up to
        ``decode_chunk * (spec_k + 1)`` tokens per slot from one host
        transfer.  Returns the rounds executed (0 when idle)."""
        with _span("serve.step", queued=len(self.queue)):
            self._admit()
            with _span("serve.upload"):
                self._sync_ptable()
                self._sync_hist()
            if not self.active.any():
                return 0
            budgets = np.asarray(
                [r.max_new_tokens if r is not None else 0 for r in self.req],
                np.int32,
            )
            counts = np.asarray([len(e) for e in self.emitted], np.int32)
            rounds = max(1, self.decode_chunk)
            with _span("serve.decode") as sp:
                if sp.is_enabled():
                    sp.set_metadata(**self._decode_args(rounds))
                rows, self.cache, dcache, self.hist = self._spec_chunk(
                    self.params, self.cache, self.draft_params,
                    self._draft_cache, jnp.asarray(self.last_token),
                    self.hist, self.base_key, jnp.asarray(self.temps),
                    jnp.asarray(self.active), jnp.asarray(counts),
                    jnp.asarray(budgets), jnp.int32(self.eos_id),
                    greedy_only=self._all_greedy(),
                )
            if self.draft is not None:
                self._draft_cache = dcache
            self._collect(rows, self._consume_spec)
            return rounds

    def run(self, max_steps: int = 10_000) -> List[Completion]:
        steps = 0
        chunked = self.engine in ("fused", "paged") and self.decode_chunk > 1
        while (self.queue or self.active.any()) and steps < max_steps:
            if self.spec_k > 0:
                steps += self.step_spec() or 1
            elif chunked:
                steps += self.step_chunk() or 1
            else:
                self.step()
                steps += 1
        return self.done

    # ------------------------------------------------------------------
    @property
    def utilization(self) -> float:
        return float(self.active.mean())

    @property
    def live_tokens(self) -> int:
        """Tokens currently resident in the KV cache across active slots
        (prompt + emitted so far)."""
        return sum(
            len(self.req[s].prompt) + len(self.emitted[s])
            for s in range(self.max_batch) if self.active[s]
        )

    def kv_stats(self) -> Dict[str, float]:
        """KV-memory accounting for the capacity claims in the bench: a
        dense engine reserves the full ``max_batch x max_seq`` rectangle
        up front, a paged engine holds ``pages_in_use x page`` tokens of
        HBM (plus whatever the pool was sized to) — memory proportional
        to live tokens, not to worst-case shape."""
        cfg = self.model.cfg
        per_tok = (2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim
                   * jnp.dtype(cfg.dtype).itemsize)
        live = self.live_tokens
        stats: Dict[str, float] = {
            "kv_bytes_per_token": per_tok,
            "live_tokens": live,
            "chunk_utilization": (self.chunk_steps_used
                                  / max(1, self.chunk_steps_total)),
        }
        if self.spec_k > 0:
            stats.update(
                spec_rounds=self.spec_rounds,
                spec_tokens=self.spec_tokens,
                spec_accepted=self.spec_accepted,
                spec_proposed=self.spec_proposed,
                spec_accept_rate=(self.spec_accepted
                                  / max(1, self.spec_proposed)),
                spec_tokens_per_round=(self.spec_tokens
                                       / max(1, self.spec_rounds)),
            )
        if self.engine == "paged":
            in_use = self.pool.pages_in_use * self.page_size * per_tok
            stats.update(
                kv_bytes_allocated=self.num_pages * self.page_size * per_tok,
                kv_bytes_in_use=in_use,
                kv_bytes_per_live_token=in_use / max(1, live),
                pages_in_use=self.pool.pages_in_use,
                pages_total=self.pool.capacity,
                prefix_hits=self.pool.prefix_hits,
                prefix_lookups=self.pool.prefix_lookups,
                prefix_hit_rate=self.pool.hit_rate,
            )
        else:
            alloc = self.max_batch * self.max_seq * per_tok
            stats.update(
                kv_bytes_allocated=alloc,
                kv_bytes_in_use=alloc,  # dense: reserved whether live or not
                kv_bytes_per_live_token=alloc / max(1, live),
            )
        return stats


def smoke_serve(model: Model, params: Pytree, *, num_requests: int,
                vocab_size: int, max_batch: int = 8, max_seq: int = 96,
                prompt_len: int = 8, max_new_tokens: int = 8,
                seed: int = 0, engine: str = "fused", decode_chunk: int = 1,
                temperature: float = 0.0, page_size: int = 16,
                num_pages: Optional[int] = None, spec_k: int = 0,
                spec_ngram_n: int = 3, draft: Optional[Model] = None,
                draft_params: Optional[Pytree] = None
                ) -> Tuple[List[Completion], Dict[str, float]]:
    """Drive one engine through a synthetic request burst and report
    throughput stats — the serving smoke used by ServeStage and quick
    engine checks.  Returns (completions, stats) where stats carries
    request/token counts and tokens/s for the metric log (plus prefix
    sharing counters when ``engine='paged'``)."""
    import time

    eng = ServeEngine(model, params, max_batch=max_batch, max_seq=max_seq,
                      seed=seed, engine=engine, decode_chunk=decode_chunk,
                      page_size=page_size, num_pages=num_pages,
                      spec_k=spec_k, spec_ngram_n=spec_ngram_n,
                      draft=draft, draft_params=draft_params)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    for i in range(num_requests):
        eng.submit(Request(uid=i,
                           prompt=rng.integers(1, vocab_size, prompt_len),
                           max_new_tokens=max_new_tokens,
                           temperature=temperature))
    completions = eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(c.tokens) for c in completions)
    stats = {"requests": len(completions), "tokens": toks,
             "step_time_s": dt, "tok_per_s": toks / max(dt, 1e-9),
             "engine": engine, "decode_chunk": decode_chunk,
             "d2h_transfers": eng.d2h_transfers,
             "chunk_utilization": (eng.chunk_steps_used
                                   / max(1, eng.chunk_steps_total))}
    if spec_k > 0:
        stats["spec_k"] = spec_k
        stats["spec_accept_rate"] = (eng.spec_accepted
                                     / max(1, eng.spec_proposed))
        stats["spec_tokens_per_round"] = (eng.spec_tokens
                                          / max(1, eng.spec_rounds))
    if engine == "paged":
        stats["prefix_hit_rate"] = eng.pool.hit_rate
        stats["prefix_hits"] = eng.pool.prefix_hits
        stats["pages_total"] = eng.pool.capacity
    return completions, stats
