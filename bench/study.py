#!/usr/bin/env python3
"""Chip studies that set the benchmark's numbers, one process each.

    python3 bench/study.py check --workload <cell> --seeds 1,2,3 [--seconds 8]
    python3 bench/study.py sweep --workload <cell> --rates 2,4,6 [--seconds 20]

``check`` reads, for each seed, the numbers the comparison uses from the
program (through the cell's own timed path, at its own size) and, on
the first ``--control-seeds`` seeds, from the control: the plain
reference in float8 put in the program's place.  For a training cell it
also reads there the planted fault that needs a run (half of the batch
left out, planted in the reference).  The limits in
``workloads/<cell>.json`` are set from these readings.

``sweep`` serves an open-loop cell at each rate in turn and prints the
throughput, the tail of time to first token and the backlog left at the
window's end: the highest rate with no growing backlog is the knee.

One JSON line per seed or rate; the set-up (weights, warm-up) is paid
once and the programs are shared across seeds.  It exits with code 3
without a TPU.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
sys.path.insert(0, ROOT)


def _emit(obj):
    print(json.dumps(obj), flush=True)


def _program(name, require_chip, **where):
    import jax

    from bench.harness import runner
    from bench.harness import spec as S
    from bench.harness.clock import CompileClock

    cell = S.load_cell(name, **where)
    runner.device_info(cell.chips, require_chip)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import compile_cache

    if require_chip:
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    return cell, runner.load_program(cell.config), clock


def study_train(cell, program, clock, seeds, seconds, n_control):
    from bench.harness import check as C
    from bench.harness import runner
    from bench.harness import weights as W
    from bench.harness.train import TrainDriver

    drv = None
    for seed in seeds:
        t0 = time.perf_counter()
        params = W.make_params(cell.config, seed)
        if drv is None:
            drv = TrainDriver(cell, seed, seconds, program, clock,
                              runner.annotate)
            drv.build(params)
        else:   # the same compiled step, a new seed's state and feed
            drv.seed = seed
            import jax.numpy as jnp

            drv.state = {"params": params,
                         "opt": program.adamw_init(params, drv.opt),
                         "step": jnp.zeros((), jnp.int32)}
            drv.next_batch = 0
        del params
        prog = drv.warm()
        drv.release()
        gc.collect()
        args = (cell.config, cell.traffic, cell.settings["optimizer"], seed,
                drv.checked)
        ref = C.train_reference(*args, precision="f32")
        row = {"seed": seed, "program": C.compare_train(prog, ref, drv.names)}
        if seeds.index(seed) < n_control:
            row["control"] = C.compare_train(
                C.train_reference(*args, precision="fp8"), ref, drv.names)
            row["half_batch"] = C.compare_train(
                C.train_reference(*args, precision="f32", fault="half_batch"),
                ref, drv.names)
        row["losses"] = {"program": prog["losses"], "reference": ref["losses"]}
        row["seconds"] = time.perf_counter() - t0
        _emit(row)


def _serve_driver(cell, program, clock, seed, seconds, engine=None,
                  mix=None):
    from bench.harness import runner
    from bench.harness import weights as W
    from bench.harness.serve import ServeDriver

    if mix is not None:
        cell.traffic = mix
    drv = ServeDriver(cell, seed, seconds, program, clock, runner.annotate)
    params = W.make_params(cell.config, seed)
    if engine is None:
        drv.build(params)
        t0 = time.perf_counter()
        _emit({"warm_up": drv.warm(), "warm_s": time.perf_counter() - t0,
               "compile": clock.snapshot()})
    else:
        drv.engine = engine
        drv.max_seq = engine.max_seq
        drv.params = params
        engine.params = params
    return drv


def _drain(engine):
    while engine.queue or engine.active.any():
        engine.step()
    engine.done.clear()


def study_serve(cell, program, clock, seeds, seconds, n_control):
    from bench.harness import check as C

    engine = None
    for seed in seeds:
        t0 = time.perf_counter()
        drv = _serve_driver(cell, program, clock, seed, seconds, engine)
        engine = drv.engine
        rec = drv.run(None)
        fin = drv.finished()
        _drain(engine)
        cs = cell.settings["check"]
        samples = C.sample(fin, seed, cs["min_served_tokens"],
                           cs["max_requests"])
        tr = drv.traffic
        got = C.served_gaps(drv.params, cell.config, samples,
                            tr.max_prompt() + tr.max_output(),
                            tr.max_output(), cs["block"],
                            control=seeds.index(seed) < n_control)
        _emit({"seed": seed, "served_gap": got["served_gap"],
               "control_gap": got.get("control_gap"), "tokens": got["tokens"],
               "requests": got["requests"],
               "compiles_in_window": rec["compiles_in_window"],
               "seconds": time.perf_counter() - t0})
        # one seed's weights at a time: the next seed's are made anew
        engine.params = None
        del drv, got
        gc.collect()


def sweep_serve(cell, program, clock, rates, seconds, seed):
    from bench.harness import spec as S

    engine = None
    base = dict(cell.traffic)
    for rate in rates:
        mix = dict(base, rate_per_s=rate)
        drv = _serve_driver(cell, program, clock, seed, seconds, engine, mix)
        engine = drv.engine
        rec = drv.run(None)
        ctx = {"rec": rec, "setup_s": 0.0}
        row = {"rate_per_s": rate, "queue_at_start": rec["queue_at_start"],
               "compiles_in_window": rec["compiles_in_window"],
               "queue_at_end": rec["queue_at_end"],
               "active_at_end": int(engine.active.sum())}
        for m in ("ttft_p95_ms", "itl_p95_ms", "serve_tokens_per_s"):
            row[m] = S.metric_reader(m)(ctx)
        win = [r for r in rec["requests"] if r["in_window"]]
        ttft = sorted(1e3 * (r["times"][0] - r["due"]) for r in win
                      if r["times"])
        row["ttft_p50_ms"] = ttft[len(ttft) // 2] if ttft else None
        row["requests"] = len(win)
        row["failed"] = sum(r["failed"] for r in win)
        late = sorted(rec["lateness_s"])
        row["late_p95_ms"] = 1e3 * late[int(0.95 * (len(late) - 1))] if late else None
        _emit(row)
        _drain(engine)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("check", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=4,
                    help="read the control (and faults) on the first N seeds")
    args = ap.parse_args(argv)
    from bench.harness import runner

    try:
        cell, program, clock = _program(args.workload, True)
    except runner.NoChip as e:
        print(f"study: {e}", file=sys.stderr)
        return 3
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.mode == "sweep":
        rates = [float(r) for r in args.rates.split(",") if r]
        sweep_serve(cell, program, clock, rates, args.seconds, seeds[0])
    elif cell.traffic["mode"] == "train":
        study_train(cell, program, clock, seeds, args.seconds,
                    args.control_seeds)
    else:
        study_serve(cell, program, clock, seeds, args.seconds,
                    args.control_seeds)
    import jax

    _emit({"memory": [d.memory_stats() for d in jax.devices()[:cell.chips]]})
    _emit({"study_done": True, "wall_s": time.perf_counter() - _T0})
    return 0


if __name__ == "__main__":
    sys.exit(main())
