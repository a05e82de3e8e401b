"""Serving driver: ``ServeEngine.submit`` / ``step`` under a traffic mix.

Set-up builds the engine on weights from the seed, warms it by
replaying the mix's own requests in groups of every size the traffic
can bring at once (``warm``), then serves ``preroll_s`` seconds of the
mix so the window starts in a steady state.  The window
then runs for ``seconds``; every token is timed on the host when
``step()`` hands it over.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench.harness import traffic as T


@dataclasses.dataclass
class ReqRec:
    uid: int
    plen: int
    max_new: int
    due: float                  # host time the request was due (open loop)
    submit: float               # host time it was submitted
    in_window: bool             # due (open) or submitted (closed) in window
    prompt: np.ndarray
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: Optional[List[int]] = None     # set once finished
    failed: bool = False


class ServeDriver:
    def __init__(self, cell, seed: int, seconds: float, program, clock,
                 annotate):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.p = program
        self.clock = clock
        self.annotate = annotate
        self.m = cell.config["model"]
        self.mix = cell.traffic
        self.server = cell.settings["server"]
        self.traffic = T.ServeTraffic(self.mix, self.m["vocab_size"], seed,
                                      seconds)
        self.recs: Dict[int, ReqRec] = {}
        self.steps: List[Dict[str, Any]] = []
        self.refused = 0
        self.setup_record: Dict[str, Any] = {}

    # ------------------------------------------------------------ set-up
    def build(self, params) -> None:
        s = self.server
        page = s["page_size"]
        need = self.traffic.max_prompt() + self.traffic.max_output() - 1
        self.max_seq = -(-need // page) * page
        self.params = params
        self.engine = self.p.ServeEngine(
            self.p.model, params, max_batch=s["max_batch"],
            max_seq=self.max_seq, eos_id=-1, seed=self.seed,
            engine=s["engine"], decode_chunk=s["decode_chunk"],
            page_size=page, spec_k=s["spec_k"])

    def warm(self) -> Dict[str, Any]:
        """Replay the mix's own requests through ``submit`` / ``step`` so
        that set-up compiles (or loads) every program the window can
        reach, whatever shapes the engine gives them.  Groups of every
        size the traffic can bring at once (1..``max_batch`` open-loop,
        up to ``admit_per_step`` closed-loop) are drawn from the length
        spread of the seed's request set: alone, up to 64 requests of
        distinct prompt lengths (every length where the set has fewer);
        in groups, 16 such requests spread evenly by length.  A mix with
        shared prefixes goes through twice: once with one prefix carried
        by every member and held by a running request (a group that
        takes every slot comes first and holds it itself: its first
        member brings it, the others find it), once with every member's
        prefix new.  Members ask for
        two tokens, so the decode step runs too; each group runs until
        only the holder is left."""
        tr, eng = self.traffic, self.engine
        V, prefix = self.m["vocab_size"], tr.prefix_tokens
        if self.mix["loop"] == "open":
            sizes = range(1, self.server["max_batch"] + 1)
        else:
            sizes = range(1, int(self.mix.get("admit_per_step", 1)) + 1)
        alone, strata = tr.spread(64), tr.spread(16)
        rng = np.random.default_rng([self.seed % (1 << 64), 99])
        uid = [-1]

        def submit(prompt, max_new):
            eng.submit(self.p.Request(uid=uid[0], prompt=prompt,
                                      max_new_tokens=max_new))
            uid[0] -= 1
            return uid[0] + 1

        def running():
            return {eng.req[s].uid for s in range(eng.max_batch)
                    if eng.active[s] and eng.req[s] is not None}

        def settle(keep):
            eng.step()
            while eng.queue or running() - {keep}:
                eng.step()

        full = self.server["max_batch"]
        groups = 0
        for held in ([True, False] if prefix else [None]):
            shared = rng.integers(0, V, prefix, dtype=np.int32)
            holder = None
            for g in sorted(sizes, key=lambda g: g < full):
                members = ([[i] for i in alone] if g == 1 else
                           [strata[k:k + g] for k in range(0, len(strata), g)])
                for group in members:
                    if held and g < full and holder not in running():
                        holder = submit(np.concatenate(
                            [shared, rng.integers(0, V, 16, dtype=np.int32)]),
                            tr.max_output())
                        settle(holder)
                    for i in group:
                        body = tr.request(i).prompt[prefix:]
                        head = (shared if held else rng.integers(
                            0, V, prefix, dtype=np.int32))
                        submit(np.concatenate([head, body]) if prefix
                               else body, 2)
                    settle(holder)
                    groups += 1
        while eng.queue or eng.active.any():
            eng.step()
        eng.done.clear()
        return {"groups": groups, "group_sizes": [sizes[0], sizes[-1]],
                "prefix_passes": 2 if prefix else 1}

    # ------------------------------------------------------------ serving
    def _submit(self, req: T.Req, due: float, now: float,
                in_window: bool) -> None:
        rec = ReqRec(uid=req.index, plen=len(req.prompt),
                     max_new=req.max_new_tokens, due=due, submit=now,
                     in_window=in_window, prompt=req.prompt)
        try:
            self.engine.submit(self.p.Request(
                uid=req.index, prompt=req.prompt,
                max_new_tokens=req.max_new_tokens))
        except ValueError:
            rec.failed = True
            self.refused += 1
        self.recs[req.index] = rec

    def _observe(self, t_start: float, t_end: float, traced: bool) -> None:
        """Time every token ``step()`` handed over, and record the step's
        useful work (prompts prefilled, cached lengths decoded)."""
        eng = self.engine
        prefills, kv = [], []
        seen = []
        for slot in range(eng.max_batch):
            req = eng.req[slot]
            if req is not None and eng.active[slot]:
                seen.append((req.uid, len(eng.emitted[slot]), None))
        while self._done_seen < len(eng.done):
            c = eng.done[self._done_seen]
            self._done_seen += 1
            seen.append((c.uid, len(c.tokens), c.tokens))
        for uid, n, toks in seen:
            rec = self.recs.get(uid)
            if rec is None:
                continue
            k = len(rec.times)
            for j in range(k + 1, n + 1):
                if j == 1:
                    prefills.append(rec.plen)
                else:
                    kv.append(rec.plen + j - 1)
                rec.times.append(t_end)
            if toks is not None:
                rec.tokens = list(toks)
        self.steps.append({"t0": t_start, "t1": t_end, "prefill": prefills,
                           "decode_kv": kv, "traced": traced})

    def _step(self, traced: bool) -> None:
        t0 = time.perf_counter()
        with self.annotate("bench.step"):
            self.engine.step()
        t1 = time.perf_counter()
        with self.annotate("bench.observe"):
            self._observe(t0, t1, traced)

    def run(self, tracer=None) -> Dict[str, Any]:
        """Pre-roll, then the window; returns the run record."""
        self._done_seen = len(self.engine.done)
        if self.mix["loop"] == "open":
            return self._run_open(tracer)
        return self._run_closed(tracer)

    def _run_open(self, tracer) -> Dict[str, Any]:
        eng, tr = self.engine, self.traffic
        T_win = self.seconds
        start = time.perf_counter()
        t_win0 = start + tr.preroll_s        # due times are from here
        nxt = 0
        window_started = False
        lateness: List[float] = []
        drain_limit = 60.0
        while True:
            now = time.perf_counter()
            if not window_started and now >= t_win0:
                window_started = True
                self.window_start = now
                self.counters0 = self._counters()
                self.compiles0 = self.clock.compiles()
                self.queue0 = len(eng.queue)
                if tracer is not None:
                    tracer.start()
            if tracer is not None and tracer.active and tracer.due(now):
                tracer.stop()
            if now >= t_win0 + T_win:
                break
            with self.annotate("bench.submit"):
                while (nxt < len(tr) and t_win0 + tr.due_s(nxt) <= now
                       and tr.due_s(nxt) < T_win):
                    req = tr.request(nxt)
                    due = t_win0 + req.due_s
                    self._submit(req, due, time.perf_counter(),
                                 in_window=req.due_s >= 0)
                    if req.due_s >= 0:
                        lateness.append(time.perf_counter() - due)
                    nxt += 1
            if eng.queue or eng.active.any():
                self._step(tracer is not None and tracer.active)
            else:
                wait = (t_win0 + tr.due_s(nxt) - time.perf_counter()
                        if nxt < len(tr) else 0.001)
                with self.annotate("bench.wait"):
                    time.sleep(max(0.0, min(wait, 0.05)))
        self.window_end = time.perf_counter()
        self.compiles1 = self.clock.compiles()
        self.queue1 = len(eng.queue)
        self.counters1 = self._counters()
        if tracer is not None and tracer.active:
            tracer.stop()
        # drain: every request due in the window gets its first token
        t_stop = self.window_end + drain_limit
        pending = [r for r in self.recs.values()
                   if r.in_window and not r.failed and not r.times]
        while pending and time.perf_counter() < t_stop:
            self._step(False)
            pending = [r for r in pending if not r.times]
        for r in pending:
            r.failed = True
        self.drain_end = time.perf_counter()
        return self._record(lateness)

    def _run_closed(self, tracer) -> Dict[str, Any]:
        eng, tr = self.engine, self.traffic
        clients = int(self.mix["clients"])
        per_step = int(self.mix.get("admit_per_step", 1))
        start = time.perf_counter()
        t_win0 = start + tr.preroll_s
        nxt = 0
        window_started = False
        lateness: List[float] = []
        while True:
            now = time.perf_counter()
            if not window_started and now >= t_win0:
                window_started = True
                self.window_start = now
                self.counters0 = self._counters()
                self.compiles0 = self.clock.compiles()
                self.queue0 = len(eng.queue)
                if tracer is not None:
                    tracer.start()
            if tracer is not None and tracer.active and tracer.due(now):
                tracer.stop()
            if window_started and now >= self.window_start + self.seconds:
                break
            with self.annotate("bench.submit"):
                in_sys = len(eng.queue) + int(eng.active.sum())
                k = 0
                while in_sys < clients and k < per_step:
                    self._submit(tr.request(nxt), now, time.perf_counter(),
                                 in_window=window_started)
                    nxt += 1
                    in_sys += 1
                    k += 1
            self._step(tracer is not None and tracer.active)
        self.window_end = time.perf_counter()
        self.compiles1 = self.clock.compiles()
        self.queue1 = len(eng.queue)
        self.counters1 = self._counters()
        if tracer is not None and tracer.active:
            tracer.stop()
        self.drain_end = self.window_end
        return self._record(lateness)

    def _counters(self) -> Dict[str, int]:
        pool = self.engine.pool
        if pool is None:
            return {}
        return {"prefix_hits": int(pool.prefix_hits),
                "prefix_lookups": int(pool.prefix_lookups)}

    def _record(self, lateness: List[float]) -> Dict[str, Any]:
        w0, w1 = self.window_start, self.window_end
        reqs = []
        for r in self.recs.values():
            reqs.append({
                "uid": r.uid, "plen": r.plen, "max_new": r.max_new,
                "due": r.due - w0, "submit": r.submit - w0,
                "in_window": r.in_window, "failed": r.failed,
                "times": [t - w0 for t in r.times],
                "finished": r.tokens is not None,
            })
        steps = [dict(s, t0=s["t0"] - w0, t1=s["t1"] - w0) for s in self.steps]
        c0, c1 = self.counters0, self.counters1
        return {
            "kind": "serve",
            "window_s": w1 - w0,
            "drain_s": self.drain_end - w1,
            "requests": reqs,
            "steps": steps,
            "counters": {k: c1[k] - c0[k] for k in c1},
            "compiles_in_window": self.compiles1 - self.compiles0,
            "queue_at_start": self.queue0,
            "queue_at_end": self.queue1,
            "lateness_s": lateness,
            "refused": self.refused,
        }

    def finished(self) -> List[Tuple[int, np.ndarray, List[int]]]:
        """(uid, prompt, served tokens) of every request that finished."""
        return [(r.uid, r.prompt, r.tokens) for r in self.recs.values()
                if r.tokens is not None and not r.failed]

    def release(self) -> None:
        """Free the engine's device state (its caches); keep the weights."""
        self.engine = None
