"""Training driver: the program's train step, jitted with its state
donated as ``launch/train.py`` calls it, fed seeded batches.

Set-up builds one object, the compiled step with its state, and drives
it through its first ``checked_steps`` steps on distinct batches of the
window's own feed, reading what the comparison needs on the way: the
loss of each step, the first gradient as the optimizer got it (from its
first moment after one step) and the parameters' change after the
checked steps.  The window then continues the same object.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import spec as S
from bench.harness import traffic as T
from bench.harness import weights as W


def leaf_names(tree) -> List[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@functools.partial(jax.jit, static_argnames=("model_items", "ref_file"))
def change_norms(params, key, *, model_items, ref_file) -> jax.Array:
    """Per-leaf norm of ``params - params0``, ``params0`` made anew from
    the seed's key (the donated original is gone)."""
    p0 = W._make(key, model_items, ref_file)
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p0))])


class TrainDriver:
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
        self.opt_cfg = cell.settings["optimizer"]
        self.checked = int(cell.settings.get("checked_steps", 3))
        self.steps: List[Dict[str, Any]] = []

    def feed(self, i: int) -> Dict[str, jax.Array]:
        with self.annotate("bench.feed"):
            return {"tokens": jnp.asarray(T.train_batch(
                self.mix, self.m["vocab_size"], self.seed, i))}

    def build(self, params) -> None:
        o = self.opt_cfg
        self.opt = self.p.OptimizerConfig(
            lr=o["lr"], betas=tuple(o["betas"]), eps=o["eps"],
            weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
            warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
            schedule="cosine", moment_dtype="float32")
        self.step_fn = self.p.jit_train_step(
            self.p.make_train_step(self.p.model, self.opt, self.p.Plan()),
            donate=True)
        self.names = leaf_names(params)
        self.state = {"params": params,
                      "opt": self.p.adamw_init(params, self.opt),
                      "step": jnp.zeros((), jnp.int32)}
        self.next_batch = 0

    def _one(self):
        batch = self.feed(self.next_batch)
        self.next_batch += 1
        with self.annotate("bench.dispatch"):
            self.state, met = self.step_fn(self.state, batch)
        return met

    def warm(self) -> Dict[str, Any]:
        """The checked steps, through the window's own call and feed."""
        losses, grad_norms, change = [], None, None
        b1 = self.opt_cfg["betas"][0]
        for i in range(self.checked):
            met = self._one()
            losses.append(float(met["loss"]))
            if i == 0:
                grad_norms = np.asarray(
                    leaf_norms(self.state["opt"]["m"])) / (1.0 - b1)
        change = np.asarray(change_norms(
            self.state["params"], W.key_from_seed(self.seed),
            model_items=W.model_items(self.m),
            ref_file=S.reference_file(self.cell.config)))
        self.readings = {"losses": losses, "grad_norms": grad_norms,
                         "change_norms": change}
        return self.readings

    def run(self, tracer=None) -> Dict[str, Any]:
        """Steps until ``seconds`` have passed, one step in flight ahead
        of the host; a step counts when its loss is on the host."""
        B, S = int(self.mix["batch"]), int(self.mix["seq"])
        self.window_start = time.perf_counter()
        self.compiles0 = self.clock.compiles()
        done = 0
        losses: List[float] = []
        traced_steps = 0
        if tracer is not None:
            tracer.start()
        prev = self._one()
        t_last = self.window_start
        while True:
            t0 = time.perf_counter()
            traced = tracer is not None and tracer.active
            if traced and tracer.due(t0):
                with self.annotate("bench.sync"):
                    losses.append(float(prev["loss"]))
                done += 1
                traced_steps += 1
                t_last = time.perf_counter()
                tracer.stop()
                prev = self._one()
                continue
            cur = self._one()
            with self.annotate("bench.sync"):
                losses.append(float(prev["loss"]))
            done += 1
            if traced:
                traced_steps += 1
            t_last = time.perf_counter()
            self.steps.append({"t0": t0 - self.window_start,
                               "t1": t_last - self.window_start})
            prev = cur
            if t_last - self.window_start >= self.seconds:
                break
        self.window_end = t_last
        self.compiles1 = self.clock.compiles()
        float(prev["loss"])          # the step in flight, not counted
        return {
            "kind": "train",
            "window_s": self.window_end - self.window_start,
            "steps_done": done,
            "tokens_per_step": B * S,
            "traced_steps": traced_steps,
            "losses": losses,
            "compiles_in_window": self.compiles1 - self.compiles0,
        }

    def release(self) -> None:
        self.state = None
