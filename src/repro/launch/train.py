"""End-to-end training driver (`adviser run` for training workloads).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --steps 200 --batch 8 --seq 128 --reduced

    # published widths, depth cut to 4 layers
    PYTHONPATH=src python -m repro.launch.train --full --layers 4 \
        --steps 3 --batch 4 --seq 1024

On the CPU container this drives reduced/small configs for real; on a
fleet the same driver runs full configs (the mesh/plan come from the
planner either way).  The loop runs inside the execution envelope:
structured logs, checkpoints, straggler watch, restart-on-failure.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_shape, reduced
from repro.configs.base import ShapeConfig
from repro.core.envelope import ExecutionEnvelope
from repro.core.provenance import ProvenanceStore
from repro.checkpoint import Checkpointer
from repro.data import DataConfig, make_stream
from repro.ft.failures import FailureSchedule
from repro.launch import compile_cache
from repro.models import build_model
from repro.parallel.sharding import Plan
from repro.train import (
    OptimizerConfig,
    init_train_state,
    jit_train_step,
    make_train_step,
)


def main(argv: Optional[List[str]] = None) -> Dict[str, Any]:
    """Train; returns ``{"run_id", "losses", "n_params", "wall_s"}``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--width", type=int, default=0,
                    help="override d_model for mid-size runs (e.g. ~100M; "
                         "reduced configs only)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut depth to this many layers (widths unchanged "
                         "with --full)")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--runs-dir", default="runs")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject failures at these steps (FT drill)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-donate", action="store_true",
                    help="disable train-state buffer donation (donation "
                         "updates the state in place; no-op on CPU)")
    args = ap.parse_args(argv)
    if args.width and not args.reduced:
        ap.error("--width cuts widths; --full keeps the published widths")
    compile_cache.enable()

    cfg = get_config(args.arch)
    if not args.reduced and args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    if args.reduced:
        over = {}
        if args.width:
            over.update(d_model=args.width, num_heads=max(4, args.width // 64),
                        num_kv_heads=max(2, args.width // 128),
                        head_dim=64, d_ff=0 if cfg.d_ff == 0 else args.width * 4,
                        vocab_size=8192)
        if args.layers:
            over["num_layers"] = args.layers
        cfg = reduced(cfg, **over)
    model = build_model(cfg)

    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    opt = OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                          total_steps=args.steps)
    plan = Plan(remat=args.remat, microbatch=args.microbatch)

    store = ProvenanceStore(args.runs_dir)
    record = store.create_run(
        template=f"cli-train-{args.arch}", template_version="0",
        config={"arch": args.arch, "cfg": dataclasses.asdict(cfg),
                "steps": args.steps, "batch": args.batch, "seq": args.seq},
        plan={"remat": args.remat, "microbatch": args.microbatch},
    )
    print(f"run: {record.run_id}")
    n_params = None

    stream = make_stream(cfg, shape, DataConfig(seed=args.seed,
                                                vocab_size=min(4096, cfg.vocab_size)))
    step_jit = jit_train_step(make_train_step(model, opt, plan),
                              donate=not args.no_donate)

    def init_fn():
        state = init_train_state(model, jax.random.PRNGKey(args.seed), opt, plan)
        nonlocal n_params
        n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
        return state

    def step_fn(state, step):
        batch = {k: jnp.asarray(v) for k, v in stream.batch_at(step).items()}
        for k in ("frames", "image_embeds"):
            if k in batch:
                batch[k] = batch[k].astype(jnp.bfloat16)
        return step_jit(state, batch)

    env = ExecutionEnvelope(
        record,
        checkpointer=Checkpointer(f"{record.artifacts_dir}/ckpt", keep=2),
        checkpoint_every=args.ckpt_every,
        failures=FailureSchedule(tuple(args.fail_at)) if args.fail_at else None,
    )
    t0 = time.time()
    state = env.run(init_state=init_fn, step_fn=step_fn, num_steps=args.steps)
    dt = time.time() - t0
    hist = record.metrics()
    losses = [h["loss"] for h in hist if "loss" in h]
    tok_s = args.batch * args.seq * len(losses) / dt
    print(f"params={n_params/1e6:.1f}M steps={len(losses)} "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"wall={dt:.1f}s ({tok_s:,.0f} tok/s) restarts={env.restarts}")
    return {"run_id": record.run_id, "losses": losses, "n_params": n_params,
            "wall_s": dt}


if __name__ == "__main__":
    main()
