#!/usr/bin/env python3
"""Where one cell's device time and idle time go, by the program's own
spans and scopes: one traced run, read with ``harness.spans``.

    python3 bench/phases.py --workload <cell> --seed <n> [--seconds 45]

Sets the cell up as ``bench/run.py`` does (weights from the seed, the
warm-up, the pre-roll), runs its window with the cell's first seconds
traced, and prints one JSON line: the cell's metrics as the traced run
reads them, the device's idle seconds by the innermost host span (the
serving engine's phases), by each gap's middle as the benchmark's
breakdown has it and split exactly, device seconds by scope, the
longest device ops with their scope paths, and the readings of
``harness.spans``.  It makes no comparison with the reference.  It
exits with code 3 without a TPU.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
sys.path.insert(0, ROOT)


def study(name, seed, seconds, *, require_chip=True, compile_cache_on=True,
          **where):
    """One traced run of cell ``name``; returns the result object.
    ``where`` (``root``, ``bench``, ``bench_dir``) finds the cell's
    files, as in ``runner.run_cell``."""
    import jax

    from bench.harness import runner
    from bench.harness import spans as SP
    from bench.harness import spec as S
    from bench.harness import trace as TR
    from bench.harness import weights as W
    from bench.harness.clock import CompileClock

    cell = S.load_cell(name, **where)
    device = runner.device_info(cell.chips, require_chip)
    with open(os.path.join(S.BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    peaks = table.get(device["kind"], next(iter(table.values())))
    with open(os.path.join(S.BENCH_DIR, "kernels.json")) as f:
        kernels = json.load(f)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if compile_cache_on:
        from repro.launch import compile_cache

        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    program = runner.load_program(cell.config)
    params = W.make_params(cell.config, seed)
    if cell.traffic["mode"] == "serve":
        from bench.harness.serve import ServeDriver as Driver
    else:
        from bench.harness.train import TrainDriver as Driver
    drv = Driver(cell, seed, seconds, program, clock, runner.annotate)
    drv.build(params)
    drv.warm()
    tracer = runner.Tracer(cell.settings.get("trace_seconds",
                                             min(seconds, 5.0)))
    rec = drv.run(tracer)
    red = SP.reduce(SP.load(TR.newest_xplane(tracer.dir), cell.chips),
                    kernels)
    tracer.cleanup()
    drv.release()

    ctx = {"rec": rec, "trace": red, "model": cell.config["model"],
           "peak": peaks, "traffic": cell.traffic,
           "setup_s": drv.window_start - runner.RUN_T0}
    metrics = {}
    for m in cell.end_to_end + cell.per_layer:
        val = S.metric_reader(m.name, where.get("bench_dir"))(ctx)
        if val is not None:
            metrics[m.name] = val
    readings = {
        "host_gap_ms_per_step": SP.host_gap_ms_per_step(red),
        "phase_idle_share": SP.phase_idle_share(red),
        "prefill_stall_ms": SP.prefill_stall_ms(red),
        "decode_kv_use": SP.decode_kv_use(red),
        "optimizer_device_ms_per_step": SP.optimizer_device_ms_per_step(
            red, rec.get("traced_steps", 0)),
        "spans": {n: len(SP.named(red, n)) for n in
                  ("serve.step",) + SP.PHASES},
    }
    return {"workload": name, "seed": seed, "device": device,
            "metrics": metrics, "readings": readings,
            "busy_s": red["busy_s"], "window_s": red["window_s"],
            "compiles_in_window": rec["compiles_in_window"],
            "idle_gaps": TR.top(red["idle_gaps"], 12),
            "idle_split": TR.top(red["idle_split"], 12),
            "device_scopes": TR.top(red["scopes"], 16),
            "device_ops": [[op, sec, red["op_scopes"].get(op, "")]
                           for op, sec in TR.top(red["ops"], 12)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args(argv)

    from bench.harness import runner

    runner.RUN_T0 = _T0
    try:
        out = study(args.workload, args.seed, args.seconds)
    except runner.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
