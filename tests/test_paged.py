"""Paged KV cache: Pallas paged-attention kernel vs oracle, the XLA
scan fallback, the page-pool allocator (refcounts + prefix registry),
and the engine-level contract — ``engine="paged"`` is token-identical
to ``engine="fused"`` while holding HBM proportional to live tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ops, ref
from repro.kernels.flash_xla import paged_attention_xla
from repro.kernels.paged_attention import pages_per_block
from repro.models import build_model
from repro.serve.engine import PagePool, Request, ServeEngine

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)


def _paged_setup(rng, B, KH, G, D, page, max_pages, num_pages, kv_len,
                 dtype=jnp.float32):
    """Random pools + a valid page table: each row maps ceil(kv_len/page)
    distinct physical pages (never page 0, the engine's null page) and
    leaves the rest unmapped (-1)."""
    H = KH * G
    q = _rand(rng, (B, 1, H, D), dtype)
    k_pool = _rand(rng, (KH, num_pages, page, D), dtype)
    v_pool = _rand(rng, (KH, num_pages, page, D), dtype)
    lens = np.asarray(kv_len, np.int32)
    table = np.full((B, max_pages), -1, np.int32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    for b in range(B):
        for lp in range(-(-int(lens[b]) // page)):
            table[b, lp] = free.pop()
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lens)


# ===========================================================================
# kernel: Pallas (interpret) and XLA fallback vs gather oracle
# ===========================================================================
# Lengths at the walk's edges for a 20-page table of 16-token pages,
# walked in blocks of 16 pages (256 tokens): an empty row, one token,
# exactly one block, one block plus one, and the full table (the second
# block, 4 pages of 16, is cut short by the table's width).
_EDGE_LENS = (0, 1, 256, 257, 320)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,KH,G,D,page,max_pages,kv_len",
    [
        pytest.param(1, 4, 1, 32, 16, 4, None, id="1-4-1-32-16-4"),   # MHA
        pytest.param(4, 2, 4, 32, 16, 4, None, id="4-2-4-32-16-4"),   # GQA
        # MQA, small pages
        pytest.param(2, 1, 8, 16, 8, 8, None, id="2-1-8-16-8-8"),
        # head_dim padded to the 128 lane
        pytest.param(2, 2, 2, 48, 16, 4, None, id="2-2-2-48-16-4"),
        # qwen2-1.5b's group (12 heads over 2 KV heads), walk edges
        pytest.param(5, 2, 6, 128, 16, 20, _EDGE_LENS, id="qwen2-G6-edges"),
        # glm4-9b's group (32 heads over 2 KV heads), walk edges
        pytest.param(5, 2, 16, 128, 16, 20, _EDGE_LENS, id="glm4-G16-edges"),
        # 8-token pages: blocks of 32 pages over a 40-page table
        pytest.param(5, 1, 6, 64, 8, 40, _EDGE_LENS, id="page8-G6-edges"),
        # several blocks, random lengths
        pytest.param(3, 2, 6, 128, 16, 52, None, id="qwen2-G6-52pages"),
    ],
)
def test_paged_kernel_matches_oracle(rng, B, KH, G, D, page, max_pages,
                                     kv_len, dtype):
    num_pages = 1 + B * max_pages
    if kv_len is None:
        kv_len = rng.integers(1, page * max_pages + 1, B)
    kv_len = np.asarray(kv_len)
    q, kp, vp, table, lens = _paged_setup(
        rng, B, KH, G, D, page, max_pages, num_pages, kv_len, dtype)
    want = ref.paged_attention(q, kp, vp, table, lens)
    ops.set_backend("interpret")
    try:
        got = ops.paged_decode_attention(q, kp, vp, table, kv_len=lens)
    finally:
        ops.set_backend("ref")
    live = kv_len > 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=TOL[dtype], rtol=TOL[dtype])
    # an empty row walks nothing and writes zeros
    np.testing.assert_array_equal(np.asarray(got, np.float32)[~live], 0.0)


@pytest.mark.parametrize("kv_len", [1, 7, 16, 64])
def test_paged_xla_fallback_matches_oracle(rng, kv_len):
    B, KH, G, D, page, max_pages = 3, 2, 2, 32, 16, 4
    q, kp, vp, table, lens = _paged_setup(
        rng, B, KH, G, D, page, max_pages, 1 + B * max_pages,
        np.full(B, kv_len))
    got = paged_attention_xla(q, kp, vp, table, lens)
    want = ref.paged_attention(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_oracle_equals_dense_gather(rng):
    """Gathering the mapped pages into a dense cache and running the
    dense decode attention is bit-identical to the paged oracle — the
    foundation of the paged==fused engine parity."""
    B, KH, G, D, page, max_pages = 2, 2, 2, 32, 8, 4
    q, kp, vp, table, lens = _paged_setup(
        rng, B, KH, G, D, page, max_pages, 1 + B * max_pages,
        np.asarray([13, 29]))
    pt = np.maximum(np.asarray(table), 0)
    k = np.asarray(kp)[:, pt].transpose(1, 2, 3, 0, 4).reshape(
        B, max_pages * page, KH, D)
    v = np.asarray(vp)[:, pt].transpose(1, 2, 3, 0, 4).reshape(
        B, max_pages * page, KH, D)
    dense = ops.decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                                 kv_len=lens)
    paged = ref.paged_attention(q, kp, vp, table, lens)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(paged))


def _scribble_dead(table, lens, pool, value):
    """``pool`` with ``value`` written over every position a row may not
    read: unmapped pages (the null page 0 among them) and, in mapped
    pages, every position at or past the row's length."""
    table, lens = np.asarray(table), np.asarray(lens)
    page = pool.shape[2]
    dead = np.ones(pool.shape[1:3], bool)  # (P, page)
    for b in range(table.shape[0]):
        for j, pid in enumerate(table[b]):
            if pid >= 0:
                dead[pid] = np.arange(j * page, (j + 1) * page) >= lens[b]
    return jnp.where(jnp.asarray(dead)[None, :, :, None],
                     jnp.asarray(value, pool.dtype), pool)


def _with_room(rng, B, KH, G, D, page, max_pages, kv_len, room, T=1):
    """``_paged_setup`` with ``room`` more tokens of pages mapped past
    each row's length (the engine's decode reservation), and a last
    row parked: unmapped, its stale length still set."""
    mapped = np.minimum(np.asarray(kv_len) + room, page * max_pages)
    q, kp, vp, table, _ = _paged_setup(
        rng, B, KH, G, D, page, max_pages, 1 + B * max_pages, mapped)
    if T > 1:
        q = _rand(rng, (B, T, KH * G, D), jnp.float32)
    table = table.at[-1].set(-1)
    return q, kp, vp, table, jnp.asarray(kv_len, jnp.int32)


def test_paged_kernel_ignores_dead_pages(rng):
    """Entries past kv_len (including -1/unmapped) must not contribute:
    scribbling over every unmapped page leaves the output unchanged.
    The Pallas kernel neither reads nor computes with them: NaN written
    over unmapped pages, over mapped pages past kv_len and over the tail
    of each row's last live page changes none of its outputs, and the
    parked row (nothing mapped) writes zeros."""
    B, KH, G, D, page, max_pages = 2, 2, 2, 32, 8, 4
    q, kp, vp, table, lens = _paged_setup(
        rng, B, KH, G, D, page, max_pages, 1 + B * max_pages,
        np.asarray([9, 20]))
    want = ref.paged_attention(q, kp, vp, table, lens)
    mapped = set(np.asarray(table)[np.asarray(table) >= 0].tolist())
    unmapped = [p for p in range(kp.shape[1]) if p not in mapped]
    kp2 = kp.at[:, jnp.asarray(unmapped)].set(1e4)
    vp2 = vp.at[:, jnp.asarray(unmapped)].set(-1e4)
    got = ref.paged_attention(q, kp2, vp2, table, lens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_xla = paged_attention_xla(q, kp2, vp2, table, lens)
    np.testing.assert_allclose(np.asarray(got_xla), np.asarray(want),
                               atol=2e-5)

    # the kernel: 8-token pages, blocks of 32 pages over a 40-page table
    lens = [5, 256, 300, 40]
    q, kp, vp, table, lens = _with_room(rng, 4, 2, 6, 128, 8, 40, lens, 20)
    ops.set_backend("interpret")
    try:
        clean = ops.paged_decode_attention(q, kp, vp, table, kv_len=lens)
        dirty = ops.paged_decode_attention(
            q, _scribble_dead(table, lens, kp, np.nan),
            _scribble_dead(table, lens, vp, np.nan), table, kv_len=lens)
    finally:
        ops.set_backend("ref")
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))
    want = ref.paged_attention(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(clean)[:-1], np.asarray(want)[:-1],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(clean)[-1], 0.0)


@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize(
    "G,page,max_pages,base_len",
    [
        # qwen2-1.5b's group; 16-token pages in blocks of 16 over 20:
        # the furthest draft row ends at 1 + T - 1, 256, 257 and 300
        pytest.param(6, 16, 20, (1, 252, 253, 296), id="qwen2-G6"),
        # glm4-9b's group; 8-token pages in blocks of 32 over 40
        pytest.param(16, 8, 40, (3, 250, 254, 316), id="glm4-G16-page8"),
    ],
)
def test_paged_verify_kernel_matches_oracle(rng, G, page, max_pages,
                                            base_len, T):
    """The verify entry walks the same pages with ``T * G`` query rows
    and per-row causal limits; at ``T == 1`` it is the decode kernel."""
    B, KH, D = len(base_len), 2, 128
    q, kp, vp, table, lens = _paged_setup(
        rng, B, KH, G, D, page, max_pages, 1 + B * max_pages,
        np.asarray(base_len) + T - 1)
    q = _rand(rng, (B, T, KH * G, D), jnp.float32)
    base = jnp.asarray(base_len, jnp.int32)
    want = ref.paged_attention_mq(q, kp, vp, table, base)
    ops.set_backend("interpret")
    try:
        got = ops.paged_decode_attention_mq(q, kp, vp, table, base_len=base)
        if T == 1:
            single = ops.paged_decode_attention(q, kp, vp, table,
                                                kv_len=base)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(single))
    finally:
        ops.set_backend("ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_verify_kernel_ignores_dead_pages(rng):
    """The verify walk at ``T = 5``: NaN past the furthest draft row's
    limit, in unmapped pages and in the parked row's null page, changes
    none of its outputs; the parked row writes zeros."""
    T = 5
    base = [1, 252, 253, 60]
    q, kp, vp, table, lens = _with_room(rng, 4, 2, 6, 128, 16, 20, base, 24,
                                        T=T)
    hi = lens + T - 1  # the furthest draft row's limit
    ops.set_backend("interpret")
    try:
        clean = ops.paged_decode_attention_mq(q, kp, vp, table, base_len=lens)
        dirty = ops.paged_decode_attention_mq(
            q, _scribble_dead(table, hi, kp, np.nan),
            _scribble_dead(table, hi, vp, np.nan), table, base_len=lens)
    finally:
        ops.set_backend("ref")
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))
    want = ref.paged_attention_mq(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(clean)[:-1], np.asarray(want)[:-1],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(clean)[-1], 0.0)


# ===========================================================================
# PagePool allocator
# ===========================================================================
def test_page_pool_alloc_free_refcount():
    pool = PagePool(num_pages=5, page_size=8)
    assert pool.capacity == 4 and pool.pages_free == 4
    a, b = pool.alloc(), pool.alloc()
    assert 0 not in (a, b) and a != b  # page 0 reserved
    assert pool.pages_in_use == 2
    pool.free(a)
    assert pool.pages_free == 3
    c = pool.alloc(chain_hash=b"h1")
    assert pool.lookup(b"h1") == c  # hit increfs
    assert pool.refs[c] == 2
    pool.free(c)
    assert pool.lookup(b"h1") == c and pool.refs[c] == 2  # still registered
    pool.free(c)
    pool.free(c)
    assert pool.refs[c] == 0 and pool.lookup(b"h1") is None  # registry drops
    assert pool.prefix_hits == 2 and pool.prefix_lookups == 3
    pool.free(b)
    assert pool.pages_free == 4
    with pytest.raises(ValueError, match="num_pages"):
        PagePool(num_pages=1, page_size=8)


def test_page_pool_exhaustion_returns_none():
    pool = PagePool(num_pages=3, page_size=8)
    assert pool.alloc() is not None and pool.alloc() is not None
    assert pool.alloc() is None  # dry, not an exception


# ===========================================================================
# engine: paged == fused, across attention-family configs
# ===========================================================================
@pytest.fixture(scope="module")
def qwen2_setup():
    cfg = reduced(get_config("qwen2-1.5b"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _run_burst(setup, *, engine, decode_chunk=1, max_batch=3, seed=0,
               temperature=0.0, prompt_lens=(6, 9, 6, 11, 7), max_new=5,
               shared_prefix=0, **engine_kw):
    cfg, model, params = setup
    eng = ServeEngine(model, params, max_batch=max_batch, max_seq=64,
                      eos_id=-1, seed=seed, engine=engine,
                      decode_chunk=decode_chunk, **engine_kw)
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, cfg.vocab_size, shared_prefix).astype(np.int32)
    for i, plen in enumerate(prompt_lens):
        tail = rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        eng.submit(Request(uid=i, prompt=np.concatenate([prefix, tail]),
                           max_new_tokens=max_new + (i % 3),
                           temperature=temperature))
    done = eng.run()
    assert len(done) == len(prompt_lens)
    return {c.uid: (tuple(c.tokens), c.finished_reason) for c in done}, eng


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "glm4-9b", "qwen3-moe"])
def test_paged_greedy_parity_with_fused(arch):
    """engine='paged' emits bit-identical greedy tokens to engine='fused'
    across attention families: GQA+bias (qwen2), dense GQA (glm4), and
    MoE (qwen3-moe — exact-length admission, no padded prefill)."""
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    setup = (cfg, model, params)
    fused, _ = _run_burst(setup, engine="fused")
    paged, eng = _run_burst(setup, engine="paged", page_size=8)
    assert paged == fused
    assert eng.pool.pages_in_use == 0  # every page returned at retire


def test_paged_chunked_matches_step(qwen2_setup):
    step, _ = _run_burst(qwen2_setup, engine="paged", page_size=8)
    for chunk in (2, 4):
        chunked, _ = _run_burst(qwen2_setup, engine="paged", page_size=8,
                                decode_chunk=chunk)
        assert chunked == step


def test_paged_temperature_parity_with_fused(qwen2_setup):
    """With a fixed slot assignment (requests == slots) the per-slot
    sample streams are keyed by (seed, slot, pos) — identical between
    the dense fused cache and the paged pool."""
    kw = dict(max_batch=4, prompt_lens=(6, 8, 7, 9), temperature=1.5,
              seed=3)
    fused, _ = _run_burst(qwen2_setup, engine="fused", **kw)
    paged, _ = _run_burst(qwen2_setup, engine="paged", page_size=8, **kw)
    assert paged == fused


def test_paged_parity_under_pool_pressure(qwen2_setup):
    """A pool too small for every request at once forces the
    requeue-at-admission path; completions still match fused exactly."""
    fused, _ = _run_burst(qwen2_setup, engine="fused")
    paged, eng = _run_burst(qwen2_setup, engine="paged", page_size=8,
                            num_pages=9)  # 8 allocatable pages
    assert paged == fused
    assert eng.pool.pages_in_use == 0


def test_paged_prefix_sharing_hits_and_refcounts(qwen2_setup):
    """Requests sharing a long prompt prefix map the same physical pages:
    the registry reports hits, fewer pages are allocated than the
    unshared sum, and outputs still match fused bit-for-bit."""
    kw = dict(max_batch=4, prompt_lens=(3, 5, 3, 4), shared_prefix=16,
              max_new=4)
    fused, _ = _run_burst(qwen2_setup, engine="fused", **kw)
    paged, eng = _run_burst(qwen2_setup, engine="paged", page_size=8, **kw)
    assert paged == fused
    assert eng.pool.prefix_hits > 0
    assert eng.pool.hit_rate > 0
    assert eng.pool.pages_in_use == 0
    assert (eng.pool.refs == 0).all()


def test_paged_prefix_pages_shared_not_copied(qwen2_setup):
    """Two identical prompts admitted together: the second request's full
    prompt pages are all registry hits, so its page table aliases the
    first's physical pages."""
    cfg, model, params = qwen2_setup
    eng = ServeEngine(model, params, max_batch=2, max_seq=64, eos_id=-1,
                      engine="paged", page_size=8)
    prompt = np.arange(1, 17, dtype=np.int32)  # exactly two full pages
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=3))
    eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=3))
    eng.step()
    assert eng.pool.prefix_hits == 2
    table = eng._ptable
    np.testing.assert_array_equal(table[0, :2], table[1, :2])  # aliased
    assert table[0, 2] != table[1, 2]  # private decode pages
    assert (eng.pool.refs[table[0, :2]] == 2).all()
    done = eng.run()
    toks = {c.uid: c.tokens for c in done}
    assert toks[0] == toks[1]  # identical prompts, identical greedy tails


def test_decode_args_count_pages_walked(qwen2_setup):
    """``serve.decode``'s ``kv_pages_walked``: each active row's pages
    at the next decode (``kv_len`` = the device cache's ``pos + 1``),
    rounded up to the paged kernel's block of 32 8-token pages; a
    parked slot walks nothing."""
    cfg, model, params = qwen2_setup
    eng = ServeEngine(model, params, max_batch=3, max_seq=600, eos_id=-1,
                      engine="paged", page_size=8)
    assert pages_per_block(8, eng._max_pages) == 32
    rng = np.random.default_rng(0)
    for uid, plen in enumerate((254, 9)):
        prompt = rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=6))
    walked = []
    for _ in range(2):
        eng.step()
        args = eng._decode_args(1)
        pos = np.asarray(eng.cache["pos"])
        kv_len = [int(pos[s]) + 1 for s in range(3) if eng.active[s]]
        assert sorted(kv_len) == sorted(
            len(eng.req[s].prompt) + len(eng.emitted[s])
            for s in range(3) if eng.active[s])
        assert args["kv_pages_walked"] == sum(
            32 * -(-n // 256) for n in kv_len)
        walked.append(args["kv_pages_walked"])
    # after one step (the prefill's token and one decoded) 254 + 2
    # tokens fill one block of 256; after two, 254 + 3 need a second
    assert walked == [32 + 32, 64 + 32]


def test_paged_memory_proportional_to_live_tokens(qwen2_setup):
    """At partial occupancy the paged engine holds pages for live tokens
    only, while dense reserves the full max_batch x max_seq rectangle —
    the ISSUE's memory-proportionality claim, in miniature."""
    cfg, model, params = qwen2_setup
    dense = ServeEngine(model, params, max_batch=8, max_seq=64, eos_id=-1,
                        engine="fused")
    paged = ServeEngine(model, params, max_batch=8, max_seq=64, eos_id=-1,
                        engine="paged", page_size=8)
    rng = np.random.default_rng(0)
    for eng in (dense, paged):
        for i in range(2):  # 25% slot occupancy
            eng.submit(Request(uid=i,
                               prompt=rng.integers(1, cfg.vocab_size, 8),
                               max_new_tokens=8))
        eng.step()
    ds, ps = dense.kv_stats(), paged.kv_stats()
    assert ds["live_tokens"] == ps["live_tokens"] > 0
    # 2 slots x 2 pages (8 prompt + 8 new - 1 -> 15 positions) of 8 tokens
    assert ps["pages_in_use"] == 4
    assert ps["kv_bytes_in_use"] == 4 * 8 * ps["kv_bytes_per_token"]
    assert ds["kv_bytes_per_live_token"] >= 4 * ps["kv_bytes_per_live_token"]


def test_paged_submit_rejects_unservable_request(qwen2_setup):
    """A request that could never fit the pool fails at submit() with the
    paged limit in the message — not later, mid-admission."""
    cfg, model, params = qwen2_setup
    eng = ServeEngine(model, params, max_batch=2, max_seq=64, eos_id=-1,
                      engine="paged", page_size=8, num_pages=4)
    with pytest.raises(ValueError, match=r"KV pages.*3 allocatable"):
        eng.submit(Request(uid=0, prompt=np.arange(1, 30, dtype=np.int32),
                           max_new_tokens=8))
    assert not eng.queue  # rejected, not queued
    # a servable request on the same engine still runs to completion
    eng.submit(Request(uid=1, prompt=np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=4))
    done = eng.run()
    assert len(done) == 1 and len(done[0].tokens) == 4


def test_paged_rejects_recurrent_families():
    cfg = reduced(get_config("xlstm-125m"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    assert not model.supports_paged_cache()
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(model, params, engine="paged")


def test_paged_requires_pow2_page_size(qwen2_setup):
    cfg, model, params = qwen2_setup
    with pytest.raises(ValueError, match="power of two"):
        ServeEngine(model, params, engine="paged", page_size=12)
