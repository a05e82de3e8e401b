"""The program's own spans and scopes in a profile, on the profiler's
clock: the serving engine's ``serve.*`` host spans with their arguments,
and the ``jax.named_scope`` scope of every device op.

An extension of ``trace``: ``load`` reads what ``trace.load`` reads
plus the ``serve.*`` spans (``host``, with ``host_args`` beside it) and
each op's scope path (``scopes`` beside ``ops``); ``reduce`` returns what
``trace.reduce`` returns, with each idle gap under the innermost
``bench.*`` or ``serve.*`` span, plus ``spans``, ``idle_split`` and
``scopes``.  The
functions at the end compute the per-layer readings of the engine's
phases and the train step's parts from that reduction.

A device op's scope is the ``tf_op`` stat of the op's event metadata,
which on a TPU holds its ``op_name`` (``jit(f)/jvp(train.loss)/lm.mlp/
dot_general:``).  ``ProfileData`` exposes the stats of events and not
those of their metadata, so ``op_scope_paths`` walks the ``XSpace``
message itself, skipping the event lines unread.
"""
from __future__ import annotations

import bisect
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

from bench.harness import trace as TR

ENGINE_PREFIX = "serve."
# the children of ``serve.step``: an idle gap under one of them is put
# down to an engine phase
PHASES = ("serve.admit", "serve.prefill", "serve.upload", "serve.decode",
          "serve.readback", "serve.retire")


def load(path: str, chips: int = 1) -> Dict[str, Any]:
    """``trace.load``'s result plus ``serve.*`` host spans (in ``host``,
    their arguments in ``host_args``) and each device op's scope path
    (``scopes``, beside ``ops``; ``""`` for an op without one)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    op_paths = op_scope_paths(path)
    devices: List[Tuple[str, Dict[str, list]]] = []
    host: List[TR.Event] = []
    host_args: List[Dict[str, Any]] = []
    for plane in data.planes:
        if TR._is_device(plane.name):
            lines = {"ops": [], "modules": [], "scopes": []}
            paths = op_paths.get(plane.name, {})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = e.name
                    if key == "ops":
                        lines["scopes"].append(paths.get(name, ""))
                        name = TR.device_op(name)
                    lines[key].append((name, int(e.start_ns), int(e.duration_ns)))
            devices.append((plane.name, lines))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((TR.HOST_PREFIX, ENGINE_PREFIX)):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
                        host_args.append(dict(e.stats))
    devices.sort(key=lambda d: TR._device_index(d[0]))
    return {"devices": [d for _, d in devices[:chips]], "host": host,
            "host_args": host_args}


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf) -> Iterable[Tuple[int, Any]]:
    """``(field number, value)`` of each field of one protobuf message;
    a length-delimited value is a ``memoryview`` of its bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_scope_paths(path: str) -> Dict[str, Dict[str, str]]:
    """For each device plane, the scope path of each op by its event
    name (and display name).  Fields read, from
    ``tsl/profiler/protobuf/xplane.proto``: XSpace planes 1; XPlane name
    2, event_metadata 4, stat_metadata 5 (maps: key 1, value 2);
    XEventMetadata name 2, display_name 4, stats 5; XStatMetadata id 1,
    name 2; XStat metadata_id 1, str_value 5, ref_value 7."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 4:
                events.append(v)
            elif g == 5:
                for h, w in _fields(v):
                    if h == 2:
                        md = dict(_fields(w))
                        stat_names[md.get(1, 0)] = _text(md.get(2, b""))
        if not TR._is_device(name):
            continue
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        paths: Dict[str, str] = {}
        for entry in events:
            for h, w in _fields(entry):
                if h != 2:
                    continue
                names, scope = [], ""
                for k, x in _fields(w):
                    if k in (2, 4):
                        names.append(_text(x))
                    elif k == 5:
                        st = dict(_fields(x))
                        if st.get(1) in tf_op:
                            scope = (_text(st[5]) if 5 in st
                                     else stat_names.get(st.get(7), ""))
                if scope:
                    # "op_name:op_type", the type empty for a JAX op
                    scope = scope.rpartition(":")[0] or scope
                    paths.update((n, scope) for n in names)
        out[name] = paths
    return out


_NAMED = re.compile(r"\w\.\w")
_INNER = re.compile(r"([\w.]+)\)*$")


def scope_key(path: str) -> str:
    """The scope an op's path puts it under: the outermost named scope,
    with the transformations around it (``transpose(jvp(train.loss))``),
    and below it the innermost ``lm.*`` scope, as ``top/lm.x``;
    ``unscoped`` for none.  Named scopes are the path's dotted parts;
    ``jit(...)`` parts and the op itself (the last part) are not."""
    parts = [p for p in path.split("/")[:-1]
             if not p.startswith("jit(") and _NAMED.search(p)]
    if not parts:
        return "unscoped"
    lm = [m.group(1) for m in map(_INNER.search, parts[1:])
          if m and m.group(1).startswith("lm.")]
    return parts[0] + ("/" + lm[-1] if lm else "")


def reduce(trace: Dict[str, Any], kernels: Dict[str, List[str]],
           window: Optional[Tuple[int, int]] = None) -> Dict[str, Any]:
    """``trace.reduce`` (its idle gaps under the innermost ``bench.*``
    or ``serve.*`` span, as ``host`` holds both) plus

    * ``spans``: the ``serve.*`` spans that overlap the window, each
      ``{"name", "t", "dur", "args"}`` in seconds from its start;
    * ``idle_split``: the idle seconds of ``idle_gaps`` split exactly
      by the innermost ``bench.*`` or ``serve.*`` span at each instant
      (``idle_gaps`` puts a whole gap under the span at its middle);
    * ``scopes``: device op seconds by ``scope_key`` (containers left
      out, averaged over devices, as ``ops``); ``op_scopes``: the scope
      path of each ``<module>/<op>``.  Both are empty for a trace
      without scopes.
    """
    red = TR.reduce(trace, kernels, window)
    t0, t1 = window or TR.window_of(trace["host"])
    args = trace.get("host_args") or [{}] * len(trace["host"])
    red["spans"] = sorted(
        ({"name": n, "t": (s - t0) / 1e9, "dur": d / 1e9, "args": a}
         for (n, s, d), a in zip(trace["host"], args)
         if n.startswith(ENGINE_PREFIX) and s < t1 and s + d > t0),
        key=lambda sp: sp["t"])
    n_dev = max(1, len(trace["devices"]))
    segments = _innermost(trace["host"])
    starts = [seg[0] for seg in segments]
    split: Dict[str, float] = {}
    for dev in trace["devices"]:
        busy = TR.union_ns((s, s + d) for _, s, d in
                           TR._clip(dev["ops"], t0, t1))
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            for name, sec in _cover(segments, starts, a, b).items():
                split[name] = split.get(name, 0.0) + sec / 1e9 / n_dev
    red["idle_split"] = split
    scopes: Dict[str, float] = {}
    op_scopes: Dict[str, str] = {}
    for dev in trace["devices"]:
        kept = [(e, p) for e, p in zip(dev["ops"], dev.get("scopes") or [])
                if p and not e[0].startswith("~")]
        ops = TR._clip([e for e, _ in kept], t0, t1)
        paths = [p for (_, s, d), p in kept if min(s + d, t1) > max(s, t0)]
        for (name, _, d), mod, path in zip(
                ops, TR._module_of(ops, dev["modules"]), paths):
            op_scopes[f"{mod}/{name}" if mod else name] = path
            key = scope_key(path)
            scopes[key] = scopes.get(key, 0.0) + d / 1e9 / n_dev
    red["scopes"] = scopes
    red["op_scopes"] = op_scopes
    return red


def _innermost(host: List[TR.Event]) -> List[Tuple[int, int, str]]:
    """``(start, end, name)`` pieces of time, each under the innermost
    host span then open (the window span left out); spans of one thread
    nest, so a stack of open spans finds it."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []        # (end, name), innermost last
    t = 0
    for name, s, d in sorted((e for e in host if e[0] != TR.WINDOW_SPAN),
                             key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            out.append((t, end, top))
            t = end
        if stack:
            out.append((t, s, stack[-1][1]))
        stack.append((s + d, name))
        t = s
    while stack:
        end, top = stack.pop()
        out.append((t, end, top))
        t = end
    return [p for p in out if p[1] > p[0]]


def _cover(segments: List[Tuple[int, int, str]], starts: List[int],
           a: int, b: int) -> Dict[str, int]:
    """Nanoseconds of [a, b) under each segment's span (``segments`` in
    time order, ``starts`` their starts); the rest under ``host: other``,
    as in ``trace``'s idle gaps."""
    out: Dict[str, int] = {}
    left = b - a
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(segments) and segments[i][0] < b:
        s, e, name = segments[i]
        ov = min(e, b) - max(s, a)
        if ov > 0:
            out[name] = out.get(name, 0) + ov
            left -= ov
        i += 1
    if left > 0:
        out["host: other"] = out.get("host: other", 0) + left
    return out


def scope_seconds(red: Dict[str, Any], top: str) -> float:
    """Device seconds of the ops under the outermost scope ``top``."""
    return sum(sec for key, sec in red["scopes"].items()
               if key.split("/", 1)[0] == top)


# ---------------------------------------------------------------- readings
def named(red: Dict[str, Any], name: str, whole: bool = False) -> List[dict]:
    """``name`` spans that start in the window (``whole``: that lie
    wholly in it)."""
    w = red["window_s"]
    return [sp for sp in red["spans"] if sp["name"] == name
            and 0 <= sp["t"] < w and (not whole or sp["t"] + sp["dur"] <= w)]


def host_gap_ms_per_step(red: Dict[str, Any]) -> Optional[float]:
    """Device-idle ms inside ``serve.*`` spans (``idle_split``) per
    ``serve.decode``."""
    n = len(named(red, "serve.decode"))
    if n == 0:
        return None
    idle = sum(sec for tag, sec in red["idle_split"].items()
               if tag.startswith(ENGINE_PREFIX))
    return 1e3 * idle / n


def phase_idle_share(red: Dict[str, Any]) -> Optional[float]:
    """Share (%) of the window's idle seconds under a child of
    ``serve.step`` (``PHASES``)."""
    idle = sum(red["idle_gaps"].values())
    if idle <= 0:
        return None
    return 100.0 * sum(red["idle_gaps"].get(p, 0.0) for p in PHASES) / idle


def prefill_stall_ms(red: Dict[str, Any]) -> Optional[float]:
    """Mean ms of the ``serve.prefill`` spans wholly in the window: how
    long every running request's next token waits on an admission."""
    spans = named(red, "serve.prefill", whole=True)
    if not spans:
        return None
    return 1e3 * sum(sp["dur"] for sp in spans) / len(spans)


def decode_kv_use(red: Dict[str, Any]) -> Optional[float]:
    """100 x live K/V tokens over the K/V positions the decode steps
    span (``kv_tokens`` / ``kv_capacity`` of ``serve.decode``)."""
    spans = named(red, "serve.decode")
    cap = sum(sp["args"].get("kv_capacity", 0) for sp in spans)
    if cap <= 0:
        return None
    return 100.0 * sum(sp["args"].get("kv_tokens", 0) for sp in spans) / cap


def optimizer_device_ms_per_step(red: Dict[str, Any],
                                 steps: int) -> Optional[float]:
    """Device ms under ``train.optimizer`` per traced train step."""
    sec = scope_seconds(red, "train.optimizer")
    if steps <= 0 or sec <= 0:
        return None
    return 1e3 * sec / steps
