"""Drive the harness end to end at a size a CPU test can hold: the
fixture cells under ``fixtures/``, no chip, no compile cache."""
from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, Optional

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def fixture_bench() -> Dict[str, Any]:
    with open(os.path.join(FIX, "BENCHMARK.json")) as f:
        return json.load(f)


def run_tiny(cell: str, seed: int = 2 ** 33 + 5, *, seconds: float = 3.0,
             hook: Optional[Callable] = None, bench=None,
             bench_dir: str = FIX, root: str = FIX) -> Dict[str, Any]:
    from bench.harness import runner

    return runner.run_cell(cell, seed, seconds, False, require_chip=False,
                           root=root, bench=bench or fixture_bench(),
                           bench_dir=bench_dir, program_hook=hook,
                           compile_cache_on=False)
