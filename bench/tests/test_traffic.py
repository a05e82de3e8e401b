"""The traffic generator: deterministic from the seed, ids over the
whole vocabulary, the same sizes for every seed."""
import numpy as np

from bench.harness import traffic as T

MIX = {"mode": "serve", "loop": "open", "rate_per_s": 5.0, "preroll_s": 2.0,
       "shared_prefix": {"count": 8, "tokens": 512, "zipf_s": 1.0},
       "prompt_tokens": {"median": 160, "sigma": 1.0, "min": 32, "max": 1024},
       "output_tokens": {"median": 96, "sigma": 0.9, "min": 16, "max": 512}}
V = 151936
BIG = 2 ** 31 + 12345  # seeds beyond 32 bits


def _all(seed, n=None):
    tr = T.ServeTraffic(MIX, V, seed, 30.0)
    return tr, [tr.request(i) for i in range(n or len(tr))]


def test_same_seed_same_requests():
    _, a = _all(BIG, 40)
    _, b = _all(BIG, 40)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_seeds_change_order_not_sizes():
    ta, a = _all(BIG)
    tb, b = _all(BIG + 1)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    # inter-arrival gaps are drawn from one fixed set, in another order
    fixed = T.exp_gaps(MIX["rate_per_s"], len(a))
    for reqs in (a, b):
        gaps = np.diff([r.due_s for r in reqs])
        assert np.all(np.min(np.abs(gaps[:, None] - fixed[None]), 1) < 1e-9)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_every_block_holds_one_value_of_each_stratum():
    rng = np.random.default_rng(3)
    v = np.arange(160)
    out = T.block_order(v, 16, rng)
    assert sorted(out) == list(v)
    for b in range(10):
        blk = out[16 * b: 16 * (b + 1)]
        assert sorted(x // 10 for x in blk) == list(range(16))
    assert not np.array_equal(out, T.block_order(v, 16, np.random.default_rng(4)))


def test_ids_cover_the_whole_vocabulary():
    _, reqs = _all(7, 200)
    ids = np.concatenate([r.prompt for r in reqs])
    assert ids.min() >= 0 and ids.max() < V
    assert ids.max() > 0.99 * V          # not capped at a small range
    assert np.mean(ids > V // 2) > 0.4
    batch = T.train_batch({"batch": 4, "seq": 1024}, V, BIG, 3)
    assert batch.shape == (4, 1024) and batch.max() > 0.99 * V
    np.testing.assert_array_equal(batch, T.train_batch(
        {"batch": 4, "seq": 1024}, V, BIG, 3))
    assert not np.array_equal(batch, T.train_batch(
        {"batch": 4, "seq": 1024}, V, BIG, 4))
    rows = {tuple(r) for r in batch}
    assert len(rows) == 4


def test_shared_prefixes_follow_zipf_and_lengths_stay_in_range():
    tr, reqs = _all(11)
    counts = np.bincount([r.prefix for r in reqs], minlength=8)
    assert counts[0] > counts[3] > counts[7] > 0
    for r in reqs:
        assert 512 + 32 <= len(r.prompt) <= 512 + 1024
        assert 16 <= r.max_new_tokens <= 512
        np.testing.assert_array_equal(r.prompt[:512],
                                      tr._prefix_tokens[r.prefix])
    assert reqs[0].due_s == -MIX["preroll_s"]
    assert all(b.due_s > a.due_s for a, b in zip(reqs, reqs[1:]))


def test_quantile_sizes_are_heavy_tailed():
    s = T.quantile_sizes(MIX["prompt_tokens"], 1000)
    assert s.min() == 32 and s.max() == 1024
    assert np.median(s) == 160 and s.mean() > np.median(s)


def test_classes_keep_their_shares_and_pair_their_lengths():
    mix = dict(MIX, shared_prefix=None, classes=[
        {"weight": 3, "prompt_tokens": {"median": 200, "sigma": 0.5,
                                        "min": 32, "max": 1024},
         "output_tokens": {"median": 200, "sigma": 0.5, "min": 100,
                           "max": 400}},
        {"weight": 1, "prompt_tokens": {"median": 6000, "sigma": 0.3,
                                        "min": 4096, "max": 8192},
         "output_tokens": {"median": 40, "sigma": 0.3, "min": 32,
                           "max": 64}}])
    tr = T.ServeTraffic(mix, V, BIG, 30.0)
    reqs = [tr.request(i) for i in range(tr.n)]
    long_ = [r for r in reqs if len(r.prompt) >= 4096]
    assert len(long_) == round(tr.n / 4)
    assert all(32 <= r.max_new_tokens <= 64 for r in long_)
    assert all(100 <= r.max_new_tokens <= 400 for r in reqs
               if len(r.prompt) < 4096)
    assert tr.max_prompt() == 8192 and tr.max_output() == 400
    # every block of 16 holds the long class in its share
    first = [len(r.prompt) >= 4096 for r in reqs[:16]]
    assert 3 <= sum(first) <= 5
    other = T.ServeTraffic(mix, V, BIG + 1, 30.0)
    assert sorted(len(other.request(i).prompt) for i in range(tr.n)) == \
        sorted(len(r.prompt) for r in reqs)


def test_bursts_leave_the_off_periods_empty():
    mix = dict(MIX, preroll_s=0.0, bursts={"on_s": 2.0, "off_s": 3.0})
    tr = T.ServeTraffic(mix, V, BIG, 30.0)
    due = np.array([tr.due_s(i) for i in range(tr.n)])
    assert np.all(np.mod(due, 5.0) < 2.0 + 1e-9)
    assert np.all(np.diff(due) > 0)
    # while on, the schedule is the steady one at ``rate_per_s``
    k = np.floor(due / 5.0)
    steady = T.ServeTraffic(dict(mix, bursts=None), V, BIG, 30.0)
    np.testing.assert_allclose(due - 3.0 * k,
                               [steady.due_s(i) for i in range(tr.n)])


def test_spread_spans_the_request_set():
    tr, reqs = _all(BIG)
    lens = [len(r.prompt) for r in reqs]
    every = tr.spread(10 ** 6)
    assert sorted(lens[i] for i in every) == sorted(set(lens))
    few = tr.spread(16)
    assert len(few) == 16
    got = [lens[i] for i in few]
    assert got == sorted(set(got))
    assert got[0] == min(lens) and got[-1] == max(lens)
