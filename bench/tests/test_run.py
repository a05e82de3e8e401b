"""Whole runs of the fixture cells on the CPU: the result line's keys,
the checks beside their limits, nothing compiled inside the window; and
a cell, configuration, reference, traffic mix (of a kind the benchmark
has no cell of yet: bursts, two request classes) and per-layer metric
added by adding files alone.  (Kept in one file so that these heavy CPU runs share one
pytest-xdist worker.)"""
import json
import os
import shutil

import pytest

from bench.harness import spec as S
from bench.tests.tiny import FIX, fixture_bench, run_tiny


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.chat", {"ttft_p95_ms", "itl_p95_ms", "setup_s"}),
    ("tiny.docs", {"serve_tokens_per_s", "setup_s"}),
    ("tiny.train", {"train_tokens_per_s", "setup_s"}),
])
def test_fixture_cell_runs_correct(cell, metrics, capsys):
    out = run_tiny(cell)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == metrics
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert out["checks"]["compiles_in_window"]["value"] == 0
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check compiles_in_window 0 limit 0")



def test_throwaway_cell_from_new_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(FIX, root, dirs_exist_ok=True)
    before = {p: open(os.path.join(FIX, p)).read() for p in
              ("configs/tiny.json", "traffic/tiny_chat.json")}
    cfg = json.load(open(os.path.join(root, "configs/tiny.json")))
    cfg["name"] = "tiny-wide"
    cfg["reference"] = "wide_ref"
    cfg["model"].update(d_model=96, num_heads=6, d_ff=192, qkv_bias=False,
                        tie_embeddings=False)
    json.dump(cfg, open(os.path.join(root, "configs/tiny-wide.json"), "w"))
    # an architecture's reference is a file of its own, found by name
    os.makedirs(os.path.join(root, "reference"))
    shutil.copy(os.path.join(S.BENCH_DIR, "reference", "dense_decoder.py"),
                os.path.join(root, "reference", "wide_ref.py"))
    mix = json.load(open(os.path.join(root, "traffic/tiny_chat.json")))
    short = {"prompt_tokens": mix.pop("prompt_tokens"),
             "output_tokens": mix.pop("output_tokens")}
    long_ = {"prompt_tokens": {"median": 40, "sigma": 0.3, "min": 32,
                               "max": 48},
             "output_tokens": {"median": 4, "sigma": 0.2, "min": 4,
                               "max": 6}}
    mix.update(rate_per_s=25.0, shared_prefix=None,
               bursts={"on_s": 0.5, "off_s": 0.25},
               classes=[dict(short, weight=3), dict(long_, weight=1)])
    json.dump(mix, open(os.path.join(root, "traffic/tiny_burst.json"), "w"))
    shutil.copy(os.path.join(root, "workloads/tiny.chat.json"),
                os.path.join(root, "workloads/tiny-wide.burst.json"))
    bench = fixture_bench()
    bench["configs"].append({"name": "tiny-wide", "source": "fixture",
                             "file": "configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-wide.burst", "config": "tiny-wide",
                               "traffic": "tiny_burst", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny-wide.burst")
    bench["per_layer"].append({
        "name": "served_requests_per_s.tiny", "unit": "1/s",
        "better": "higher", "source": "host_clock", "layer": "scheduler",
        "moves": "ttft_p95_ms", "workloads": ["tiny-wide.burst"]})
    out = run_tiny("tiny-wide.burst", bench=bench, bench_dir=root, root=root)
    assert out["correct"] is True, out["checks"]
    cell = S.load_cell("tiny-wide.burst", root=root, bench=bench,
                       bench_dir=root)
    assert S.reference(cell.config).__file__ == os.path.join(
        root, "reference", "wide_ref.py")
    assert set(out["metrics"]) == {"ttft_p95_ms", "setup_s"}
    from bench.harness import runner
    traced = runner.run_cell("tiny-wide.burst", 5, 1.0, True,
                             require_chip=False, root=root, bench=bench,
                             bench_dir=root, compile_cache_on=False)
    assert traced["metrics"]["served_requests_per_s.tiny"]["value"] > 0
    for p, text in before.items():
        assert open(os.path.join(FIX, p)).read() == text
