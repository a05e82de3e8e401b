#!/usr/bin/env python3
"""Bring-up check on the TPU: serve and train through the user's entry
points at published widths, with compiled Pallas kernels.

    python chip_smoke.py             # one chip: serve, logits, train
    python chip_smoke.py --chips 4   # four chips: FSDP train step only

Everything runs in this one process (a chip belongs to one process), and
the script refuses to start unless JAX's first device is a TPU.  Phases:

  serve   ``serve-qwen2-1.5b`` at ``scale=full`` through ``run_workflow``
          (the path of ``run serve-qwen2-1.5b --override scale=full``):
          engine ``fused``, then ``paged`` with ``serve_spec_k=4``.
  logits  one decode step of the same model through the paged Pallas
          kernel against the dense XLA path, same prompts and cache.
  train   ``launch.train --full --layers 4``: qwen2-1.5b widths, depth
          cut to 4 of 28 layers, batch 4 x 1024 tokens.
  fsdp    (``--chips 4`` only) the full 28-layer train step of
          ``launch.cells.build_cell`` on a 4-chip FSDP mesh; its step-0
          loss against a one-device forward of the same params and batch.

Each phase prints one JSON line with its wall and compile time (trace +
lower + backend compile, from JAX's monitoring events).  The last line
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any
failed phase raises, so the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "qwen2-1.5b"
# Paged vs dense decode logits, and FSDP vs one-device loss, differ only
# in reduction order over bf16 activations: allow a few bf16 ulps
# (2**-8 relative) of the value's scale.
BF16_RTOL = 2e-2


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, summed over
    every jit in this process since construction."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.total += duration


# ---------------------------------------------------------------------------
# phases (importable; each returns a dict of what it checked)
# ---------------------------------------------------------------------------
def serve_phase(runs_dir: str, scale: str = "full",
                smoke_batch: int = 4) -> dict:
    """Serve a few requests through ``run_workflow`` with engine
    ``fused``, then ``paged`` with speculative k=4; every request must
    complete."""
    from repro.core.provenance import ProvenanceStore
    from repro.core.workflow import REGISTRY, run_workflow

    template = REGISTRY.get(f"serve-{ARCH}").with_overrides(scale=scale)
    store = ProvenanceStore(runs_dir)
    out = {}
    for engine, spec_k in (("fused", 0), ("paged", 4)):
        res = run_workflow(template, store, serve_engine=engine,
                           serve_spec_k=spec_k, smoke_batch=smoke_batch)
        done = res.final_state
        _check(res.ok, f"serve {engine}: checks failed: {res.checks}")
        _check(len(done) == 2 * smoke_batch,
               f"serve {engine}: {len(done)} of {2 * smoke_batch} completed")
        _check(all(c.tokens for c in done), f"serve {engine}: empty completion")
        out[engine] = {"requests": len(done),
                       "tokens": sum(len(c.tokens) for c in done)}
    return out


def _paged_from_dense(cache: dict, page: int) -> dict:
    """The dense cache ``{k, v: (L, B, S, KH, D), pos}`` as a page pool:
    slot ``b``'s logical page ``j`` is pool page ``1 + b * S/page + j``
    (page 0 is the engine's null page)."""
    import jax.numpy as jnp

    L, B, S, KH, D = cache["k"].shape
    n = S // page

    def pool(x):
        x = x.reshape(L, B, n, page, KH, D).transpose(0, 4, 1, 2, 3, 5)
        x = x.reshape(L, KH, B * n, page, D)
        return jnp.concatenate([jnp.zeros_like(x[:, :, :1]), x], axis=2)

    table = (1 + jnp.arange(B * n, dtype=jnp.int32)).reshape(B, n)
    return {"k_pool": pool(cache["k"]), "v_pool": pool(cache["v"]),
            "page_table": table, "pos": cache["pos"]}


def logits_phase(scale: str = "full", batch: int = 4, prompt_len: int = 16,
                 page: int = 16, seed: int = 0) -> dict:
    """One decode step after the same prefill: paged cache (the paged
    attention kernel) against the dense cache (the XLA path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models import build_model

    cfg = get_config(ARCH)
    cfg = cfg if scale == "full" else reduced(cfg)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 1, cfg.vocab_size)
    max_seq = 2 * page * (-(-prompt_len // page))
    prefill = jax.jit(functools.partial(model.prefill, max_seq=max_seq))
    decode = jax.jit(model.decode_step)
    logits0, cache = prefill(params, tokens)
    nxt = jnp.argmax(logits0, axis=-1).astype(jnp.int32)[:, None]
    dense, _ = decode(params, cache, nxt)
    paged, _ = decode(params, _paged_from_dense(cache, page), nxt)
    dense = np.asarray(dense, np.float32)
    paged = np.asarray(paged, np.float32)
    _check(np.isfinite(dense).all() and np.isfinite(paged).all(),
           "non-finite decode logits")
    diff = float(np.abs(paged - dense).max())
    ref_scale = float(np.abs(dense).max())
    _check(diff <= BF16_RTOL * ref_scale,
           f"paged vs dense logits: max |diff| {diff} > "
           f"{BF16_RTOL} x max |logit| {ref_scale}")
    return {"max_abs_diff": diff, "max_abs_logit": ref_scale,
            "rel": diff / ref_scale}


def train_phase(runs_dir: str, full: bool = True, layers: int = 4,
                batch: int = 4, seq: int = 1024, steps: int = 3) -> dict:
    """A few steps through ``launch.train.main``; losses must be finite."""
    from repro.launch import train

    argv = ["--arch", ARCH, "--layers", str(layers), "--batch", str(batch),
            "--seq", str(seq), "--steps", str(steps), "--runs-dir", runs_dir,
            "--ckpt-every", "0"]
    if full:
        argv.insert(0, "--full")
    res = train.main(argv)
    losses = res["losses"]
    _check(len(losses) == steps, f"train: {len(losses)} of {steps} steps")
    _check(all(math.isfinite(x) for x in losses), f"train: losses {losses}")
    return {"losses": losses, "n_params": res["n_params"]}


def fsdp_phase(chips: int = 4, batch: int = 4, seq: int = 512,
               seed: int = 0) -> dict:
    """Full-depth train step on a ``chips``-way FSDP mesh; its step-0
    loss against a one-device forward of the same params and batch."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.parallel import hints
    from repro.train import OptimizerConfig, init_train_state

    _check(jax.device_count() >= chips,
           f"fsdp: {jax.device_count()} devices, {chips} needed")
    mesh = make_mesh((chips, 1), ("data", "model"),
                     devices=jax.devices()[:chips])
    cell = build_cell(ARCH, ShapeConfig("fsdp_smoke", seq, batch, "train"),
                      mesh)
    state_sh, batch_sh = cell.in_shardings
    model = build_model(get_config(ARCH))
    state = jax.jit(
        lambda: init_train_state(model, jax.random.PRNGKey(seed),
                                 OptimizerConfig()),
        out_shardings=state_sh)()
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq),
                                1, model.cfg.vocab_size)
    new_state, metrics = cell.fn(
        state, {"tokens": jax.device_put(tokens, batch_sh["tokens"])})
    loss_fsdp = float(metrics["loss"])
    del new_state

    # one device: the same params gathered onto device 0, no mesh hints
    dev0 = jax.devices()[0]
    params = jax.device_put(state["params"], dev0)
    del state
    hints.clear()
    loss_one = float(jax.jit(model.loss)(
        params, {"tokens": jax.device_put(tokens, dev0)})[0])
    _check(math.isfinite(loss_fsdp) and math.isfinite(loss_one),
           f"fsdp: losses {loss_fsdp}, {loss_one}")
    _check(abs(loss_fsdp - loss_one) <= BF16_RTOL * abs(loss_one),
           f"fsdp step-0 loss {loss_fsdp} vs one device {loss_one}")
    return {"loss_fsdp": loss_fsdp, "loss_one_device": loss_one,
            "abs_diff": abs(loss_fsdp - loss_one), "chips": chips,
            "mesh": dict(mesh.shape)}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the FSDP train step across 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is "
              f"{devices[0].platform}); refusing to run", file=sys.stderr)
        return 1

    from repro.kernels import ops
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    clock = CompileClock()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({"device": device, "kernel_backend": ops.get_backend(),
                      "compile_cache": cache_dir}), flush=True)
    _check(ops.get_backend() == "tpu",
           f"kernel backend {ops.get_backend()!r} on a TPU")

    def phase(name, fn, *a, **kw):
        # the previous phase's models may sit in reference cycles (engine
        # closures): free their device buffers before this one allocates
        gc.collect()
        t0, c0 = time.perf_counter(), clock.total
        out = fn(*a, **kw)
        print(json.dumps({"phase": name,
                          "wall_s": time.perf_counter() - t0,
                          "compile_s": clock.total - c0, **out}),
              flush=True)

    runs_root = os.path.join(REPO, "runs")
    os.makedirs(runs_root, exist_ok=True)
    runs_dir = tempfile.mkdtemp(prefix="chip_smoke-", dir=runs_root)
    try:
        if args.chips == 4:
            phase("fsdp", fsdp_phase, chips=4)
        else:
            phase("serve", serve_phase, runs_dir)
            phase("logits", logits_phase)
            phase("train", train_phase, runs_dir)
        _check(ops.get_backend() == "tpu", "kernel backend changed")
    finally:
        shutil.rmtree(runs_dir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
