"""Profiler capture and its reduction to device time.

``capture(dir)`` wraps ``jax.profiler`` around a window; ``load(path)``
reads the ``.xplane.pb`` it wrote into plain event lists; ``reduce``
turns those into the device's busy time, the time of each device
operation, each program (XLA module) and each kernel, and the idle gaps
tagged by the benchmark's own host spans (``bench.*`` annotations).
Everything after ``load`` works on plain lists, so it is tested on a
small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start ns, duration ns

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


_HLO = re.compile(r"^%?([^\s=]+)\s*=")
_CONTAINER = re.compile(r"\s(while|conditional|call)\(")


def op_name(text: str) -> str:
    """The instruction name of a device op event (the trace prints the
    whole HLO instruction, ``%name = type opcode(...)``)."""
    m = _HLO.match(text)
    return m.group(1) if m else text


def is_container(text: str) -> bool:
    """A ``while``, ``conditional`` or ``call`` op, whose interval holds
    the ops of its body: counted in busy time, not in per-op time."""
    return bool(_CONTAINER.search(text.split("=", 1)[-1][:4000]))


def device_op(text: str) -> str:
    """The name ``reduce`` keys a device op event by: its instruction
    name, with a leading ``~`` for a container op."""
    return ("~" if is_container(text) else "") + op_name(text)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def load(path: str, chips: int = 1) -> Dict[str, Any]:
    """Read one ``.xplane.pb`` into ``{"devices": [{"ops", "modules"}],
    "host": [...]}``; ``ops`` and ``modules`` are the events of the
    device's "XLA Ops" and "XLA Modules" lines (ops by instruction name,
    container ops marked with a leading ``~``), ``host`` the ``bench.*``
    spans of any host thread."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[Tuple[str, Dict[str, List[Event]]]] = []
    host: List[Event] = []
    for plane in data.planes:
        if _is_device(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = e.name
                    if key == "ops":
                        name = device_op(name)
                    lines[key].append((name, int(e.start_ns), int(e.duration_ns)))
            devices.append((plane.name, lines))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    devices.sort(key=lambda d: _device_index(d[0]))
    return {"devices": [d for _, d in devices[:chips]], "host": host}


def _device_index(name: str) -> int:
    m = re.search(r"(\d+)$", name)
    return int(m.group(1)) if m else 0


def union_ns(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], t0: int, t1: int) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def window_of(host: Sequence[Event]) -> Tuple[int, int]:
    spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _module_of(ops: Sequence[Event], modules: Sequence[Event]) -> List[str]:
    """The name of the module whose interval holds each op's start."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for _, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1] + mods[i][2]:
            out.append(mods[i][0])
        else:
            out.append("")
    return out


def reduce(trace: Dict[str, Any], kernels: Dict[str, List[str]],
           window: Optional[Tuple[int, int]] = None) -> Dict[str, Any]:
    """Device time inside the traced window, averaged over devices:

    * ``window_s``, ``busy_s`` (union of op intervals), ``idle_share``;
    * ``ops``: seconds per ``<module>/<op>``; ``modules``: seconds per
      module; ``kernels``: seconds and call count per kernel family
      (``kernels`` maps a family to regexes of op names);
    * ``module_kernels``: which kernel families each module ran;
    * ``idle_gaps``: idle seconds between busy intervals, by the
      innermost ``bench.*`` host span at the gap's middle.
    """
    t0, t1 = window or window_of(trace["host"])
    host = sorted(trace["host"], key=lambda e: e[1])
    pats = {fam: [re.compile(p) for p in ps] for fam, ps in kernels.items()}
    n = max(1, len(trace["devices"]))
    busy = 0.0
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    fam_s: Dict[str, float] = {f: 0.0 for f in kernels}
    fam_n: Dict[str, int] = {f: 0 for f in kernels}
    module_fams: Dict[str, set] = {}
    gaps: Dict[str, float] = {}
    for dev in trace["devices"]:
        dops = _clip(dev["ops"], t0, t1)
        dmods = _clip(dev["modules"], t0, t1)
        owner = _module_of(dops, dev["modules"])
        for (name, s, d), mod in zip(dops, owner):
            if name.startswith("~"):
                continue
            key = f"{mod}/{name}" if mod else name
            ops[key] = ops.get(key, 0.0) + d / 1e9 / n
            for fam, ps in pats.items():
                if any(p.search(name) for p in ps):
                    fam_s[fam] += d / 1e9 / n
                    fam_n[fam] += 1
                    module_fams.setdefault(mod, set()).add(fam)
        for name, s, d in dmods:
            modules[name] = modules.get(name, 0.0) + d / 1e9 / n
        merged = union_ns((s, s + d) for _, s, d in dops)
        busy += sum(e - s for s, e in merged) / 1e9 / n
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            tag = _host_at(host, (a + b) // 2)
            gaps[tag] = gaps.get(tag, 0.0) + (b - a) / 1e9 / n
    window_s = (t1 - t0) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy,
        "idle_share": 1.0 - busy / window_s if window_s > 0 else None,
        "ops": ops,
        "modules": modules,
        "kernels": {f: {"seconds": fam_s[f], "calls": fam_n[f]}
                    for f in kernels},
        "module_kernels": {m: sorted(f) for m, f in module_fams.items()},
        "idle_gaps": gaps,
    }


def _host_at(host: Sequence[Event], t: int) -> str:
    """Innermost (latest-starting) ``bench.*`` span holding ``t``,
    the window span only if nothing narrower does."""
    best = None
    for name, s, d in host:
        if s > t:
            break
        if s <= t < s + d and name != WINDOW_SPAN:
            best = name
    return best or "host: other"


def top(d: Dict[str, float], k: int = 10) -> List[List[Any]]:
    return [[name, sec] for name, sec in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def module_seconds(red: Dict[str, Any], family: str) -> float:
    """Seconds of the modules that ran a kernel of ``family``."""
    mods = [m for m, fams in red["module_kernels"].items() if family in fams]
    return sum(red["modules"].get(m, 0.0) for m in mods)
