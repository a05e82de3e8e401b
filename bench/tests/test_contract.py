"""BENCHMARK.json keeps to its contract, every piece it names exists,
and the command refuses to run without a chip."""
import json
import os
import re
import subprocess
import sys

from bench.harness import spec as S

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ROOT = S.REPO_DIR
B = S.load_benchmark()


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert B["paths"] == ["bench"]
    assert 1 <= len(B["command"]) <= 32 and all(_line(w) for w in B["command"])
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    assert (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 \
        <= 43200


def test_names_units_and_entries():
    seen = set()
    for kind, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                       ("workloads", {"name", "config", "traffic", "chips", "why"}),
                       ("end_to_end", {"name", "unit", "better", "bound",
                                       "source", "workloads"}),
                       ("per_layer", {"name", "unit", "better", "source",
                                      "layer", "moves", "workloads"})):
        for e in B[kind]:
            assert set(e) <= keys and set(e) >= keys - {"workloads"}, e
            assert NAME.match(e["name"]) and (kind, e["name"]) not in seen
            seen.add((kind, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e and kind in ("configs", "workloads", "per_layer"):
                    assert _line(e[k]), (e["name"], k)
    metric_names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_cells_and_configs_resolve():
    configs = {c["name"]: c for c in B["configs"]}
    used = set()
    for w in B["workloads"]:
        assert w["chips"] in (1, 4)
        used.add(w["config"])
        cell = S.load_cell(w["name"])
        assert cell.traffic["mode"] in ("serve", "train")
        assert cell.config["name"] == w["config"]
        assert all(NAME.match(k) for k in configs[w["config"]]["reduced"])
        assert configs[w["config"]]["reduced"] == cell.config["reduced"]
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert used == set(configs)
    assert len({c["file"] for c in B["configs"]}) == len(configs)
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_have_readers_and_moves():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert callable(S.metric_reader(m["name"]))
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert "workloads" not in moved or w in moved["workloads"]
        assert callable(S.metric_reader(m["name"]))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    layers = {m["layer"] for m in B["per_layer"]}
    assert layers <= {"scheduler", "model step", "kernels", "device"}


def test_command_refuses_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    w = B["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", w, "--seed", str(2 ** 40), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
