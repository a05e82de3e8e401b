"""Compile accounting from JAX's monitoring events: seconds spent
tracing, lowering and compiling, how many programs were compiled by the
backend, and persistent-cache hits."""
from __future__ import annotations

import threading
from typing import Dict

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileClock:
    """Totals over every jit of the constructing thread since
    construction (other threads of the process, such as a test suite's
    leftovers, are not counted)."""

    def __init__(self):
        from jax import monitoring

        self._thread = threading.get_ident()
        self.names = []     # what each counted trace or compile was of
        self.seconds = {TRACE: 0.0, LOWER: 0.0, BACKEND: 0.0}
        self.count = {TRACE: 0, LOWER: 0, BACKEND: 0, CACHE_HIT: 0,
                      CACHE_MISS: 0}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, fun_name: str = "?",
                     **_) -> None:
        if threading.get_ident() != self._thread:
            return
        if event in self.seconds:
            self.seconds[event] += duration
            self.count[event] += 1
            if event in (TRACE, BACKEND):
                self.names.append(fun_name)

    def _on_event(self, event: str, **_) -> None:
        if threading.get_ident() != self._thread:
            return
        if event in self.count:
            self.count[event] += 1

    def snapshot(self) -> Dict[str, float]:
        return {
            "compile_s": sum(self.seconds.values()),
            "traces": self.count[TRACE],
            "backend_compiles": self.count[BACKEND],
            "cache_hits": self.count[CACHE_HIT],
            "cache_misses": self.count[CACHE_MISS],
        }

    def compiles(self) -> int:
        """Programs traced or compiled so far: a new one inside the
        measured window is a shape the warm-up missed."""
        return self.count[TRACE] + self.count[BACKEND]

    def compiled_since(self, mark: int) -> Dict[str, int]:
        """How often each function was traced or compiled since
        ``compiles()`` read ``mark``."""
        out: Dict[str, int] = {}
        for name in self.names[mark:]:
            out[name] = out.get(name, 0) + 1
        return out
