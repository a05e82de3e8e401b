#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for.  Earlier output lines carry the set-up record, the traffic
generator's lateness and the comparison's detail; the last line is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; ``checks`` comes
last.  With no TPU, or fewer chips than the cell asks for, it exits
with code 3 and prints no result.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the compile cache lives in the checkout, at a fixed path, whatever the
# machine's environment says: set before JAX is imported
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# JAX writes cache entries only into a directory that exists
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import runner

    runner.RUN_T0 = _T0
    try:
        out = runner.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace))
    except runner.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
