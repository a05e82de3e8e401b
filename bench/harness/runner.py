"""One run of one cell: set-up, window, trace reduction, comparison and
the result line.  ``bench/run.py`` is its command line."""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Any, Callable, Dict, Optional

from bench.harness import spec as S

RUN_T0 = time.perf_counter()


class NoChip(RuntimeError):
    pass


class Tracer:
    """Profiles the first ``seconds`` of the window, inside one
    ``bench.window`` host span."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.active = False
        self.done = False

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def due(self, now: float) -> bool:
        return now - self.t0 >= self.seconds

    def stop(self) -> None:
        import jax

        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def load_program(config: Dict[str, Any]) -> types.SimpleNamespace:
    """The system under test: the program's model for this configuration,
    its serving engine and its train step."""
    src = os.path.join(S.REPO_DIR, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.configs import get_config
    from repro.models import build_model
    from repro.parallel.sharding import Plan
    from repro.serve.engine import Request, ServeEngine
    from repro.train import (OptimizerConfig, adamw_init, jit_train_step,
                             make_train_step)

    base = get_config(config["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    over = {k: v for k, v in config["model"].items() if k in fields}
    cfg = dataclasses.replace(base, **over)
    for k, v in over.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"configuration key {k}: program runs "
                             f"{getattr(cfg, k)!r}, file states {v!r}")
    return types.SimpleNamespace(
        cfg=cfg, model=build_model(cfg), ServeEngine=ServeEngine,
        Request=Request, OptimizerConfig=OptimizerConfig,
        adamw_init=adamw_init, jit_train_step=jit_train_step,
        make_train_step=make_train_step, Plan=Plan)


def device_info(chips: int, require_chip: bool) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX sees {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(stats) -> Optional[int]:
    """Peak bytes on the fullest chip, from each chip's
    ``memory_stats()``: buffers at their peak (``peak_bytes_in_use``)
    plus what the runtime reserved beside them for the programs'
    temporaries (``peak_bytes_reserved``), which the first leaves out on
    a TPU; at most ``bytes_limit``."""
    peaks = []
    for st in stats:
        if "peak_bytes_in_use" in st:
            both = int(st["peak_bytes_in_use"]) + int(
                st.get("peak_bytes_reserved", 0))
            # the two peaks need not coincide: never more than the chip
            peaks.append(min(both, int(st.get("bytes_limit", both))))
    return max(peaks) if peaks else None


def _emit(obj: Dict[str, Any], stream=None) -> None:
    print(json.dumps(obj), file=stream or sys.stdout, flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, root: str = S.REPO_DIR,
             bench: Optional[Dict[str, Any]] = None,
             bench_dir: Optional[str] = None,
             program_hook: Optional[Callable] = None,
             compile_cache_on: bool = True) -> Dict[str, Any]:
    """Run cell ``name`` once and return the result object (the last
    line).  ``program_hook(program)`` may replace parts of the system
    under test (the fault tests break the timed path with it);
    ``require_chip=False`` and ``compile_cache_on=False`` let the tests
    drive a whole run on the CPU."""
    import jax

    from bench.harness import check as C
    from bench.harness import trace as TR
    from bench.harness import weights as W
    from bench.harness.clock import CompileClock

    cell = S.load_cell(name, root=root, bench=bench, bench_dir=bench_dir)
    device = device_info(cell.chips, require_chip)
    with open(os.path.join(S.BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device["kind"] not in table and require_chip:
        raise NoChip(f"no peaks for device kind {device['kind']!r}")
    # off the chip (tests) the first entry stands in
    peaks = table.get(device["kind"], next(iter(table.values())))

    src = os.path.join(S.REPO_DIR, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.launch import compile_cache

    cache_dir = None
    if compile_cache_on:
        cache_dir = compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    program = load_program(cell.config)
    if program_hook is not None:
        program_hook(program)
    model = cell.config["model"]
    W.check_layout(jax.eval_shape(lambda k: program.model.init(k)[0],
                                  jax.random.PRNGKey(0)), cell.config)
    params = W.make_params(cell.config, seed)
    jax.block_until_ready(params)
    t_params = time.perf_counter()

    mode = cell.traffic["mode"]
    if mode == "serve":
        from bench.harness.serve import ServeDriver as Driver
    else:
        from bench.harness.train import TrainDriver as Driver
    drv = Driver(cell, seed, seconds, program, clock, annotate)
    drv.build(params)
    compiles0 = clock.compiles()
    warmed = drv.warm()
    setup = {"compile": clock.snapshot(), "compile_cache": cache_dir,
             "params_s": t_params - RUN_T0,
             "warmed": warmed if mode == "serve" else {
                 "checked_steps": drv.checked},
             "compiles_in_warm_up": clock.compiles() - compiles0}
    tracer = None
    if trace:
        tracer = Tracer(cell.settings.get("trace_seconds",
                                          min(seconds, 5.0)))
    rec = drv.run(tracer)
    setup_s = drv.window_start - RUN_T0
    setup["setup_s"] = setup_s
    setup["compile_window"] = rec["compiles_in_window"]
    setup["compiled_in_window"] = clock.compiled_since(drv.compiles0)
    _emit({"setup": setup})
    if mode == "serve":
        late = sorted(rec["lateness_s"])
        _emit({"generator": {
            "submitted_in_window": len(late),
            "late_p50_ms": 1e3 * late[len(late) // 2] if late else None,
            "late_p95_ms": 1e3 * late[int(0.95 * (len(late) - 1))] if late
            else None,
            "late_max_ms": 1e3 * late[-1] if late else None}})
    stats = [d.memory_stats() or {} for d in jax.devices()[:cell.chips]]
    _emit({"memory": stats})
    device["memory_peak_bytes"] = memory_peak(stats)

    red = None
    if tracer is not None:
        with open(os.path.join(S.BENCH_DIR, "kernels.json")) as f:
            kernels = json.load(f)
        raw = TR.load(TR.newest_xplane(tracer.dir), cell.chips)
        red = TR.reduce(raw, kernels)
        tracer.cleanup()
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]

    # the comparison: after the window, with the program's state freed
    t_check = time.perf_counter()
    checks = {}
    finished = drv.finished() if mode == "serve" else None
    prog_readings = getattr(drv, "readings", None)
    names = getattr(drv, "names", None)
    drv.release()
    gc.collect()
    lim = cell.settings["limits"]
    if mode == "serve":
        cs = cell.settings["check"]
        samples = C.sample(finished, seed, cs["min_served_tokens"],
                           cs["max_requests"])
        tr = drv.traffic
        got = C.served_gaps(params, cell.config, samples,
                            tr.max_prompt() + tr.max_output(),
                            tr.max_output(), cs["block"])
        checks["served_gap"] = {"value": got["served_gap"],
                                "limit": lim["served_gap"]}
        checks["served_tokens"] = {"value": got["tokens"],
                                   "limit": cs["min_served_tokens"],
                                   "at_least": True}
        detail = got
    else:
        del params
        gc.collect()
        ref = C.train_reference(cell.config, cell.traffic,
                                cell.settings["optimizer"], seed,
                                drv.checked)
        cmp_ = C.compare_train(prog_readings, ref, names)
        for k in lim:
            checks[k] = {"value": cmp_[k], "limit": lim[k]}
        detail = {"program": {k: (v.tolist() if hasattr(v, "tolist") else v)
                              for k, v in prog_readings.items()},
                  "reference": {k: (v.tolist() if hasattr(v, "tolist")
                                    else v) for k, v in ref.items()},
                  **{k: cmp_[k] for k in ("grad_worst_leaf",
                                          "change_worst_leaf", "left_out")}}
    checks["compiles_in_window"] = {"value": rec["compiles_in_window"],
                                    "limit": 0}
    detail["check_s"] = time.perf_counter() - t_check
    detail["window_s"] = rec["window_s"]
    _emit({"check_detail": detail})

    ctx = {"rec": rec, "trace": red, "model": model, "peak": peaks,
           "traffic": cell.traffic, "setup_s": setup_s}
    def read(ms):
        out = {}
        for m in ms:
            val = S.metric_reader(m.name, bench_dir)(ctx)
            if val is not None:
                out[m.name] = {"value": val, "unit": m.unit}
        return out

    e2e = read(cell.end_to_end)
    # a traced run reports its per-layer metrics; its end-to-end numbers,
    # on an earlier line, give the tracing overhead
    _emit({"end_to_end": e2e, "traced": bool(trace)})
    metrics = read(cell.per_layer) if trace else e2e

    correct = all((c["value"] >= c["limit"]) if c.get("at_least")
                  else (c["value"] <= c["limit"]) for c in checks.values())
    if rec["kind"] == "serve":
        win = [r for r in rec["requests"] if r["in_window"]]
        attempted, failed = len(win), sum(r["failed"] for r in win)
    else:
        attempted = rec["steps_done"]
        failed = sum(1 for x in rec["losses"] if x != x)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        out["breakdown"] = {"device_ops": TR.top(red["ops"]),
                            "idle_gaps": TR.top(red["idle_gaps"])}
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}"
              + (" (at least)" if v.get("at_least") else ""),
              file=sys.stderr, flush=True)
    return out
