"""Cell lowering: build the jit-able step + shardings for one
(architecture × shape × mesh × plan) assignment cell.

Shared by the multi-pod dry-run (launch/dryrun.py), the roofline
benchmarks and the perf-iteration loop.  No jax device-state side effects
at import time.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_shape, shape_applicable
from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import build_model
from repro.models.api import Model
from repro.parallel.sharding import (
    Plan,
    batch_specs,
    cache_specs_sharding,
    make_param_shardings,
)
from repro.train import OptimizerConfig, make_train_artifacts

Pytree = Any


def default_plan(cfg: ModelConfig, mesh: Mesh, *, remat: str = "full",
                 microbatch: int = 1, **kw) -> Plan:
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return Plan(
        name="baseline",
        dp_axes=dp,
        fsdp_axes=dp,
        remat=remat,
        microbatch=microbatch,
        **kw,
    )


@dataclasses.dataclass
class LoweredCell:
    arch: str
    shape: str
    mesh_desc: str
    kind: str
    fn: Any  # the jitted function (un-lowered)
    args: Tuple  # ShapeDtypeStruct args to lower with
    plan: Plan
    in_shardings: Tuple  # where fn expects each arg (to place real arrays)


def build_cell(arch: str, shape: Union[str, ShapeConfig], mesh: Mesh,
               plan: Optional[Plan] = None,
               opt_cfg: Optional[OptimizerConfig] = None) -> LoweredCell:
    """``shape`` is a name from ``configs.SHAPES`` or a ShapeConfig."""
    cfg = get_config(arch)
    if isinstance(shape, str):
        shape = get_shape(shape)
    shape_name = shape.name
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise ValueError(f"{arch} × {shape_name}: {why}")
    model = build_model(cfg)
    plan = plan or default_plan(cfg, mesh)
    opt_cfg = opt_cfg or OptimizerConfig()
    mesh_desc = "x".join(str(s) for s in mesh.devices.shape)

    if shape.kind == "train":
        art = make_train_artifacts(model, mesh, plan, opt_cfg, shape)
        fn = jax.jit(
            art.step_fn,
            in_shardings=(art.state_shardings, art.batch_shardings),
            out_shardings=(art.state_shardings, None),
        )
        return LoweredCell(arch, shape_name, mesh_desc, "train", fn,
                           (art.state_specs, art.batch_input_specs), plan,
                           (art.state_shardings, art.batch_shardings))

    # serving paths use bf16 parameters
    from repro.parallel import hints as act_hints
    from repro.models import moe as moe_mod
    from repro.kernels import ops as kernel_ops

    kernel_ops.set_attn_impl(plan.attn_impl)
    kernel_ops.set_ssm_chunk(plan.ssm_chunk)
    kernel_ops.set_flash_blocks(plan.flash_block_q, plan.flash_block_k)
    act_hints.install(mesh, dp_axes=plan.dp_axes,
                      seq_shard_attn=plan.seq_shard_attn)
    if cfg.num_experts > 0:
        mdl = tuple(a for a in ("model",) if a in mesh.shape)
        dp = tuple(a for a in plan.dp_axes if a in mesh.shape)

        def hint(x):
            spec = P(dp or None, mdl or None, *([None] * (x.ndim - 2)))
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

        moe_mod.set_moe_sharding_hint(hint)
        moe_mod.set_moe_impl(plan.moe_impl, mesh, plan.dp_axes)

    p_specs, axes = model.param_specs()
    p_specs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
        if s.dtype == jnp.float32 else s, p_specs
    )
    p_shard = make_param_shardings(mesh, axes, p_specs, plan)

    if shape.kind == "prefill":
        b_specs = model.input_specs(shape)
        b_shard = batch_specs(b_specs, mesh, plan)
        cache_spec = jax.eval_shape(
            lambda p, b: model.prefill(p, b["tokens"], b)[1], p_specs, b_specs
        )
        cache_shard = cache_specs_sharding(cache_spec, mesh, plan,
                                           shape.global_batch, shape.seq_len)

        def prefill_fn(params, batch):
            return model.prefill(params, batch["tokens"], batch)

        fn = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard),
                     out_shardings=(None, cache_shard))
        return LoweredCell(arch, shape_name, mesh_desc, "prefill", fn,
                           (p_specs, b_specs), plan, (p_shard, b_shard))

    # decode
    specs = model.input_specs(shape)
    cache_spec = specs["cache"]
    tok_spec = specs["tokens"]
    cache_shard = cache_specs_sharding(cache_spec, mesh, plan,
                                       shape.global_batch, shape.seq_len)
    tok_shard = batch_specs({"tokens": tok_spec}, mesh, plan)["tokens"]

    def decode_fn(params, cache, tokens):
        return model.decode_step(params, cache, tokens)

    fn = jax.jit(decode_fn, in_shardings=(p_shard, cache_shard, tok_shard),
                 out_shardings=(None, cache_shard))
    return LoweredCell(arch, shape_name, mesh_desc, "decode", fn,
                       (p_specs, cache_spec, tok_spec), plan,
                       (p_shard, cache_shard, tok_shard))


# ===========================================================================
# Collective-byte accounting from the partitioned HLO
# ===========================================================================
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}
_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9]+(?:,[0-9]+)*)")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Sum *operand* bytes of every collective in the per-device program.

    Result shapes are parsed from the ins; all-gather results are divided
    by the group size (operand = result/g), reduce-scatter multiplied.
    ``-done`` ops are skipped so async pairs are not double-counted.
    """
    by_kind: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        eq = line.find(" = ")
        if eq < 0:
            continue
        rhs = line[eq + 3:]
        kind = None
        op_at = -1
        for k in _COLL_KINDS:
            i = rhs.find(k)
            while i >= 0:
                rest = rhs[i + len(k):]
                if rest.startswith("(") or rest.startswith("-start("):
                    if op_at < 0 or i < op_at:
                        kind, op_at = k, i
                    break
                if rest.startswith("-done("):
                    break  # completion of an async op — payload counted at -start
                i = rhs.find(k, i + 1)
        if kind is None:
            continue
        # result type(s) sit between '=' and the op name
        type_str = rhs[:op_at]
        rb = _shape_bytes(type_str)
        gsize = 1
        gm = _GROUPS_IOTA_RE.search(line)
        if gm:
            gsize = int(gm.group(2))
        else:
            gm2 = _GROUPS_RE.search(line)
            if gm2:
                gsize = len(gm2.group(1).split(","))
        if kind == "all-gather":
            ob = rb / max(gsize, 1)
        elif kind == "reduce-scatter":
            ob = rb * max(gsize, 1)
        else:
            ob = rb
        by_kind[kind] = by_kind.get(kind, 0.0) + ob
        count[kind] = count.get(kind, 0) + 1
    return {
        "operand_bytes_by_kind": by_kind,
        "op_count_by_kind": count,
        "total_operand_bytes": sum(by_kind.values()),
        "total_ops": sum(count.values()),
    }


def analyze_compiled(compiled) -> Dict[str, Any]:
    """Extract memory/cost/collective stats from a compiled executable."""
    out: Dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    out[k] = int(v)
    except Exception as e:  # pragma: no cover - backend-dependent
        out["memory_analysis_error"] = str(e)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        if ca:
            out["flops"] = float(ca.get("flops", 0.0))
            out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
            out["transcendentals"] = float(ca.get("transcendentals", 0.0))
    except Exception as e:  # pragma: no cover
        out["cost_analysis_error"] = str(e)
    text = compiled.as_text()
    out["collectives"] = parse_collectives(text)
    out["hlo_bytes"] = len(text)
    try:
        from repro.launch.hlo_stats import analyze_hlo

        out["hlo_stats"] = analyze_hlo(text)
    except Exception as e:  # pragma: no cover
        out["hlo_stats"] = {"error": str(e)}
    return out
