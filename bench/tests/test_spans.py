"""The engine's spans and the program's scopes, read from a profile: the
reduction of ``harness.spans`` against ``trace``'s on the small recorded
trace, the ``tf_op`` reader on a hand-made ``XSpace``, scope keys, the
readings, a paged engine traced on the CPU and the ``phases`` study on
a fixture cell."""
import glob

import jax
import numpy as np
import pytest

from bench.harness import spans as SP
from bench.harness import spec as S
from bench.harness import trace as TR
from bench.tests.test_trace import KERNELS, _synthetic

MS = 1_000_000


def _with_engine_spans():
    """``_synthetic()`` with the engine's spans inside its first
    ``bench.step``: the idle gap [4, 6) ms falls in ``serve.retire``."""
    trace = _synthetic()
    spans = [("serve.step", 0, 7 * MS, {"queued": 2}),
             ("serve.admit", 0, 1 * MS, {"admitted": 1, "uids": "7"}),
             ("serve.prefill", int(0.2 * MS), int(0.7 * MS),
              {"rows": 1, "bucket": 16, "prompt_tokens": 9}),
             ("serve.decode", 1 * MS, 3 * MS,
              {"rows": 1, "batch": 4, "kv_tokens": 10, "kv_capacity": 256,
               "steps": 1}),
             ("serve.readback", 4 * MS, int(0.5 * MS), {}),
             ("serve.retire", int(4.5 * MS), int(1.5 * MS),
              {"finished": 1, "uids": "7"}),
             # a decode that starts after the window: not counted
             ("serve.decode", 21 * MS, 1 * MS,
              {"rows": 4, "batch": 4, "kv_tokens": 999, "kv_capacity": 256,
               "steps": 1})]
    trace["host_args"] = [{} for _ in trace["host"]]
    for name, s, d, args in spans:
        trace["host"].append((name, s, d))
        trace["host_args"].append(args)
    return trace


def test_reduce_puts_gap_under_engine_phase_and_keeps_numbers():
    old = TR.reduce(_synthetic(), KERNELS)
    new = SP.reduce(_with_engine_spans(), KERNELS)
    for key in ("window_s", "busy_s", "idle_share", "ops", "modules",
                "kernels", "module_kernels"):
        assert new[key] == old[key], key
    gaps = new["idle_gaps"]
    assert gaps["serve.retire"] == pytest.approx(0.002)
    assert "bench.step" not in gaps
    assert gaps["bench.observe"] == old["idle_gaps"]["bench.observe"]
    assert sum(gaps.values()) == pytest.approx(sum(old["idle_gaps"].values()))
    # split exactly: the gap [4, 6) ms is half a ms of readback, then retire
    assert new["idle_split"] == {"serve.readback": pytest.approx(0.0005),
                                 "serve.retire": pytest.approx(0.0015),
                                 "bench.observe": pytest.approx(0.002),
                                 "bench.wait": pytest.approx(0.009)}
    idle_share = S.metric_reader("idle_share.chat")
    assert idle_share({"trace": new}) == idle_share({"trace": old})
    assert new["scopes"] == {} and new["op_scopes"] == {}
    assert [sp["name"] for sp in new["spans"]][:2] == ["serve.step",
                                                      "serve.admit"]
    assert all(sp["t"] < new["window_s"] for sp in new["spans"])


def test_readings_of_engine_spans():
    red = SP.reduce(_with_engine_spans(), KERNELS)
    # 2 ms idle in serve.* spans over one decode in the window
    assert SP.host_gap_ms_per_step(red) == pytest.approx(2.0)
    assert SP.prefill_stall_ms(red) == pytest.approx(0.7)
    assert SP.decode_kv_use(red) == pytest.approx(100 * 10 / 256)
    assert SP.phase_idle_share(red) == pytest.approx(100 * 2 / 13)
    assert SP.optimizer_device_ms_per_step(red, 3) is None


def test_readings_of_a_trace_without_engine_spans_are_none():
    red = SP.reduce(_synthetic(), KERNELS)
    assert red["spans"] == []
    for read in (SP.host_gap_ms_per_step, SP.prefill_stall_ms,
                 SP.decode_kv_use):
        assert read(red) is None
    assert SP.phase_idle_share(red) == 0.0


def test_scopes_by_outermost_scope_and_lm_scope():
    trace = _synthetic()
    paths = {"fusion.1": "jit(train_step)/train.optimizer/mul",
             "flash_attention_bhsd.1":
                 "jit(train_step)/jvp(train.loss)/lm.attn/pallas_call",
             "fusion.2": "jit(train_step)/add"}
    dev = trace["devices"][0]
    dev["scopes"] = [paths.get(name, "") for name, _, _ in dev["ops"]]
    red = SP.reduce(trace, KERNELS)
    assert red["scopes"] == {
        "train.optimizer": pytest.approx(0.002),
        "jvp(train.loss)/lm.attn": pytest.approx(0.003),
        "unscoped": pytest.approx(0.001)}
    assert SP.scope_seconds(red, "train.optimizer") == pytest.approx(0.002)
    assert SP.optimizer_device_ms_per_step(red, 2) == pytest.approx(1.0)
    assert red["op_scopes"]["jit_prefill/fusion.1"] == paths["fusion.1"]


@pytest.mark.parametrize("path,key", [
    ("jit(train_step)/jvp(train.loss)/lm.embed/jit(_take)/gather",
     "jvp(train.loss)/lm.embed"),
    ("jit(train_step)/transpose(jvp(train.loss))/while/body/closed_call/"
     "lm.mlp/dot_general", "transpose(jvp(train.loss))/lm.mlp"),
    ("jit(train_step)/jvp(train.loss)/reduce_sum", "jvp(train.loss)"),
    ("jit(train_step)/train.optimizer/mul", "train.optimizer"),
    ("jit(decode_and_sample)/while/body/lm.attn/pallas_call", "lm.attn"),
    ("jit(train_step)/add", "unscoped"),
])
def test_scope_key(path, key):
    assert SP.scope_key(path) == key


# -------------------------------------------------- a hand-made XSpace
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(num, val):
    return _varint(num << 3) + _varint(val)


def _msg(num, *parts):
    body = b"".join(p.encode() if isinstance(p, str) else p for p in parts)
    return _varint(num << 3 | 2) + _varint(len(body)) + body


def _plane(name, events, stat_names):
    stats = b"".join(_msg(5, _int(1, k), _msg(2, _int(1, k), _msg(2, n)))
                     for k, n in stat_names.items())
    md = b"".join(_msg(4, _int(1, i), _msg(2, _int(1, i), *fields))
                  for i, fields in enumerate(events, 1))
    line = _msg(3, _int(1, 5), _msg(2, "XLA Ops"), b"\x19" + bytes(8))
    return _msg(1, _int(1, 9), _msg(2, name), line, md, stats)


def test_op_scope_paths_from_the_metadata_stats(tmp_path):
    stat_names = {3: "hlo_category", 7: "tf_op",
                  8: "jit(f)/jvp(train.loss)/lm.mlp/dot_general:"}
    text = "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop"
    tpu = _plane("/device:TPU:0", [
        (_msg(2, text), _msg(4, "fusion.1"),
         _msg(5, _int(1, 3), _msg(5, "loop fusion")),
         _msg(5, _int(1, 7), _msg(5, "jit(f)/train.optimizer/mul:"))),
        (_msg(2, "%dot.2 = f32[4]{0} dot()"), _msg(5, _int(1, 7), _int(7, 8))),
        (_msg(2, "%copy.3 = f32[4]{0} copy()"),)], stat_names)
    host = _plane("/host:CPU", [
        (_msg(2, "serve.step"), _msg(5, _int(1, 7), _msg(5, "x/y.z/w:")))],
        stat_names)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(tpu + host + _msg(4, "hostname"))
    got = SP.op_scope_paths(str(path))
    assert got == {"/device:TPU:0": {
        text: "jit(f)/train.optimizer/mul",
        "fusion.1": "jit(f)/train.optimizer/mul",
        "%dot.2 = f32[4]{0} dot()": "jit(f)/jvp(train.loss)/lm.mlp/dot_general"}}


# -------------------------------------------------- the engine, traced
@pytest.fixture(scope="module")
def qwen2_tiny():
    from repro.configs import get_config, reduced
    from repro.models import build_model

    model = build_model(reduced(get_config("qwen2-1.5b")))
    params, _ = model.init(jax.random.PRNGKey(0))
    return model, params


def test_paged_engine_spans_in_a_profile(qwen2_tiny, tmp_path):
    """Span names, their nesting under ``serve.step``, and arguments that
    match the engine's own state at the moment each span describes."""
    from repro.serve import Request, ServeEngine

    model, params = qwen2_tiny
    eng = ServeEngine(model, params, max_batch=3, max_seq=64, eos_id=-1,
                      engine="paged", page_size=16)
    rng = np.random.default_rng(0)
    lens = (6, 20, 9, 7)
    for i, n in enumerate(lens):
        eng.submit(Request(uid=10 + i, prompt=rng.integers(1, 500, n),
                           max_new_tokens=3 + i % 2))
    seen = []                      # engine state at each decode dispatch
    decode = eng._decode_sample

    def spy(*a, **k):
        seen.append((int(eng.active.sum()), eng.live_tokens))
        return decode(*a, **k)

    eng._decode_sample = spy
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.queue or eng.active.any():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    raw = SP.load(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)[0])
    spans = sorted((s, n, s + d, a) for (n, s, d), a in
                   zip(raw["host"], raw["host_args"])
                   if n.startswith("serve."))
    spans = [(n, s, e, a) for s, n, e, a in spans]
    names = {n for n, *_ in spans}
    assert names == {"serve.step", "serve.admit", "serve.prefill",
                     "serve.upload", "serve.decode", "serve.readback",
                     "serve.retire"}
    steps = [(s, e) for n, s, e, _ in spans if n == "serve.step"]
    for n, s, e, _ in spans:
        assert any(a <= s and e <= b for a, b in steps), n
        if n == "serve.prefill":
            assert any(a <= s and e <= b for m, a, b, _ in spans
                       if m == "serve.admit")

    def of(name):
        return [a for n, _, _, a in spans if n == name]

    admits = [a for a in of("serve.admit") if a["admitted"]]
    assert sum(a["admitted"] for a in admits) == len(lens)
    assert sum(a["prompt_tokens"] for a in admits) == sum(lens)
    assert sorted(int(u) for a in admits for u in str(a["uids"]).split()) \
        == [10, 11, 12, 13]
    assert all(a["queue_wait_ms"] >= 0 for a in admits)
    assert sum(a["prompt_tokens"] for a in of("serve.prefill")) == sum(lens)
    decodes = of("serve.decode")
    assert [(a["rows"], a["kv_tokens"]) for a in decodes] == seen
    assert all(a["batch"] == 3 and a["kv_capacity"] == 3 * 64
               and a["steps"] == 1 for a in decodes)
    assert sum(a["finished"] for a in of("serve.retire")) == len(lens)
    assert of("serve.step")[0]["queued"] == len(lens)
    # one readback, the (B,) tokens, per decode step: the spans read
    # nothing more from the device
    assert eng.d2h_transfers == len(decodes) == len(of("serve.readback"))
    assert eng.d2h_elems == 3 * len(decodes)


def test_phases_study_on_a_fixture_cell():
    from bench import phases
    from bench.tests.tiny import FIX, fixture_bench

    out = phases.study("tiny.chat", 2 ** 33 + 11, 2.0, require_chip=False,
                       compile_cache_on=False, root=FIX,
                       bench=fixture_bench(), bench_dir=FIX)
    r = out["readings"]
    assert r["spans"]["serve.decode"] > 0 and r["spans"]["serve.prefill"] > 0
    assert r["spans"]["serve.step"] == r["spans"]["serve.upload"]
    assert 0 < r["decode_kv_use"] <= 100 and r["prefill_stall_ms"] > 0
    assert out["compiles_in_window"] == 0
    assert set(out["metrics"]) >= {"itl_p95_ms", "setup_s"}
