"""Train-step factory: loss → grads → AdamW, with microbatch accumulation,
remat policy, MoE sharding hints and optional gradient-compression
numerics — all driven by the planner's :class:`Plan`.

The factory returns everything the launcher (or the dry-run) needs to jit
with explicit shardings:

    art = make_train_artifacts(model, mesh, plan, opt_cfg, shape)
    jit(art.step_fn, in_shardings=(art.state_shardings, art.batch_shardings),
        out_shardings=(art.state_shardings, None))
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.api import Model
from repro.models import moe as moe_mod
from repro.parallel.sharding import Plan, batch_specs, make_param_shardings
from repro.train.optimizer import OptimizerConfig, adamw_init, adamw_update
from repro.train import compression

Pytree = Any


def init_train_state(model: Model, rng: jax.Array, opt_cfg: OptimizerConfig,
                     plan: Optional[Plan] = None) -> Pytree:
    params, _ = model.init(rng)
    state = {
        "params": params,
        "opt": adamw_init(params, opt_cfg),
        "step": jnp.zeros((), jnp.int32),
    }
    if plan is not None and plan.compress_grads:
        state["grad_err"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
    return state


def make_train_step(model: Model, opt_cfg: OptimizerConfig, plan: Plan,
                    mesh: Optional[Mesh] = None) -> Callable:
    cfg = model.cfg

    from repro.kernels import ops as kernel_ops

    kernel_ops.set_attn_impl(plan.attn_impl)
    kernel_ops.set_ssm_chunk(plan.ssm_chunk)
    kernel_ops.set_flash_blocks(plan.flash_block_q, plan.flash_block_k)
    if mesh is not None:
        from repro.parallel import hints as act_hints

        act_hints.install(mesh, dp_axes=plan.dp_axes,
                          seq_shard_attn=plan.seq_shard_attn)
        if cfg.num_experts > 0:
            dp = tuple(a for a in plan.dp_axes if a in mesh.shape)
            mdl = tuple(a for a in ("model",) if a in mesh.shape)

            def hint(x):
                spec = P(dp or None, mdl or None, *([None] * (x.ndim - 2)))
                return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

            moe_mod.set_moe_sharding_hint(hint)
            moe_mod.set_moe_impl(plan.moe_impl, mesh, plan.dp_axes)
    else:
        from repro.parallel import hints as act_hints

        act_hints.clear()
        moe_mod.set_moe_sharding_hint(None)
        moe_mod.set_moe_impl("scatter")

    # named scopes name the step's parts in a profile: the forward is
    # ``jvp(train.loss)``, the backward ``transpose(jvp(train.loss))``
    def loss_of(params, batch):
        with jax.named_scope("train.loss"):
            return model.loss(params, batch, remat=plan.remat)

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def compute_grads(params, batch):
        nm = plan.microbatch
        if nm <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        split = jax.tree.map(
            lambda x: x.reshape((nm, x.shape[0] // nm) + x.shape[1:]), batch
        )
        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, mb):
            g_acc, l_acc = acc
            (loss, metrics), grads = grad_fn(params, mb)
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / nm, g_acc, grads
            )
            return (g_acc, l_acc + loss / nm), metrics

        (grads, loss), metrics = jax.lax.scan(body, (zero_g, 0.0), split)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss, metrics, grads

    def train_step(state: Pytree, batch: Pytree) -> Tuple[Pytree, Dict[str, Any]]:
        params = state["params"]
        loss, metrics, grads = compute_grads(params, batch)

        new_err = None
        if plan.compress_grads:
            # error-feedback int8 compression numerics (transport-level
            # int8 cross-pod reduce is modeled in the planner cost model)
            def comp(g, e):
                (q, s), r = compression.compress_residual(g.astype(jnp.float32) + e)
                return compression.dequantize_int8(q, s, g.shape, g.dtype), r

            pairs = jax.tree.map(comp, grads, state["grad_err"])
            grads = jax.tree.map(lambda t: t[0], pairs,
                                 is_leaf=lambda t: isinstance(t, tuple))
            new_err = jax.tree.map(lambda t: t[1], pairs,
                                   is_leaf=lambda t: isinstance(t, tuple))

        with jax.named_scope("train.optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(
                grads, state["opt"], params, opt_cfg)
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        if new_err is not None:
            new_state["grad_err"] = new_err
        out_metrics = dict(metrics)
        out_metrics.update(opt_metrics)
        return new_state, out_metrics

    return train_step


_donation_warning_filtered = False


def jit_train_step(step_fn: Callable, donate: bool = True) -> Callable:
    """Jit a train step with the state buffers donated (``donate_argnums=0``).

    The returned train state reuses the input state's memory instead of
    allocating a fresh copy every step — on accelerators this halves the
    optimizer-state working set and removes a full state copy from the
    hot loop.  Safe with the execution envelope: the checkpointer
    snapshots device->host *synchronously* before the next step runs, so
    a donated buffer is never read after invalidation.  On backends with
    no donation support at all (CPU) jax falls back to copying and warns
    about the unusable buffers; that warning is suppressed (once,
    message-matched, **CPU only** — XLA raises it at execution time,
    outside any scope we could wrap) because there the fallback is the
    expected behavior, not a bug.  On accelerator backends the warning
    is left alone: an unusable donated buffer there is real signal."""
    import warnings

    global _donation_warning_filtered

    if not donate:
        return jax.jit(step_fn)
    if not _donation_warning_filtered and jax.default_backend() == "cpu":
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        _donation_warning_filtered = True
    return jax.jit(step_fn, donate_argnums=(0,))


@dataclasses.dataclass
class TrainArtifacts:
    step_fn: Callable
    state_specs: Pytree
    state_shardings: Pytree
    batch_input_specs: Pytree
    batch_shardings: Pytree


def make_train_artifacts(model: Model, mesh: Mesh, plan: Plan,
                         opt_cfg: OptimizerConfig, shape: ShapeConfig
                         ) -> TrainArtifacts:
    """Everything needed to jit/lower the train step with explicit
    shardings — used by the launcher and the multi-pod dry-run."""
    param_specs, axes = model.param_specs()
    p_shard = make_param_shardings(mesh, axes, param_specs, plan)

    moment_dt = jnp.dtype(opt_cfg.moment_dtype)
    mom_specs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, moment_dt), param_specs
    )
    state_specs = {
        "params": param_specs,
        "opt": {
            "m": mom_specs,
            "v": mom_specs,
            "count": jax.ShapeDtypeStruct((), jnp.int32),
        },
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    rep = NamedSharding(mesh, P())
    state_shardings = {
        "params": p_shard,
        "opt": {"m": p_shard, "v": p_shard, "count": rep},
        "step": rep,
    }
    if plan.compress_grads:
        state_specs["grad_err"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), param_specs
        )
        state_shardings["grad_err"] = p_shard

    b_specs = model.input_specs(shape)
    b_shard = batch_specs(b_specs, mesh, plan)
    step_fn = make_train_step(model, opt_cfg, plan, mesh)
    return TrainArtifacts(step_fn, state_specs, state_shardings, b_specs, b_shard)
