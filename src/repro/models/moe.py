"""Mixture-of-Experts layer: top-k routing + sort-based capacity dispatch.

Dispatch strategy (TPU/SPMD-native):

  * tokens are grouped by sequence (group = one batch row), so the sort
    that builds the expert-contiguous order stays *local* to the data
    shard — no global sort collective;
  * dispatched buffers are laid out ``(groups, experts, capacity, d)`` and
    sharded (data, model) — the groups→experts resharding is exactly the
    MoE all-to-all, inserted by GSPMD at the sharding-constraint boundary;
  * expert FFN is a batched einsum over the expert axis (sharded over
    ``model``).  On TPU the same contraction is served by the
    ``kernels/moe_gmm.py`` ragged kernel (no capacity padding) through a
    shard_map wrapper; the einsum path is the XLA fallback and the
    dry-run/lowering path.

Overflowed tokens (beyond ``capacity``) are dropped (standard GShard
behaviour); the router aux loss keeps load balanced so drops stay rare.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamBuilder, activation


def init_moe(pb: ParamBuilder, cfg: ModelConfig, num_layers: int):
    L, D, E, F = num_layers, cfg.d_model, cfg.num_experts, cfg.d_ff
    pb.p("router", (L, D, E), ("layers", "embed", "experts"))
    pb.p("moe_wg", (L, E, D, F), ("layers", "experts", "embed", "mlp"))
    pb.p("moe_wu", (L, E, D, F), ("layers", "experts", "embed", "mlp"))
    pb.p("moe_wd", (L, E, F, D), ("layers", "experts", "mlp", "embed"))


def moe_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    cap = int(tokens_per_group * cfg.top_k * cfg.moe_capacity_factor / cfg.num_experts)
    return max(cap, cfg.top_k)


def apply_moe(
    p: Dict[str, Any],
    x: jax.Array,  # (B, S, D) normed — one group per batch row
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux_loss scalar)."""
    if _moe_impl == "shard_map" and _moe_mesh is not None:
        return apply_moe_shardmap(p, x, cfg)
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    C = moe_capacity(cfg, S)
    dt = x.dtype

    logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (B, S, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)  # (B, S, K)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # ---- aux load-balance loss (Switch-style) ---------------------------
    density = jnp.mean(jax.nn.one_hot(expert_ids[..., 0], E), axis=(0, 1))
    density_prob = jnp.mean(probs, axis=(0, 1))
    aux = jnp.sum(density * density_prob) * E * cfg.router_aux_weight

    # ---- sort-based dispatch (vmapped per group) ------------------------
    def dispatch_group(xg, eid, gv):
        # xg: (S, D); eid/gv: (S, K)
        M = S * K
        flat_e = eid.reshape(M)
        flat_g = gv.reshape(M)
        src = jnp.repeat(jnp.arange(S), K)
        order = jnp.argsort(flat_e)  # stable
        se, ss, sg = flat_e[order], src[order], flat_g[order]
        # position within expert segment
        starts = jnp.searchsorted(se, jnp.arange(E), side="left")  # (E,)
        pos = jnp.arange(M) - starts[se]
        keep = pos < C
        slot_e = jnp.where(keep, se, 0)
        slot_c = jnp.where(keep, pos, C)  # overflow -> dropped row C
        buf = jnp.zeros((E, C + 1, D), dt)
        buf = buf.at[slot_e, slot_c].add(jnp.where(keep[:, None], xg[ss], 0))
        return buf[:, :C], (ss, slot_e, slot_c, sg, keep)

    buf, meta = jax.vmap(dispatch_group)(x, expert_ids, gate_vals)  # (B,E,C,D)

    # groups sharded over data, experts over model: GSPMD inserts the a2a
    buf = _moe_sharding_hint(buf)

    h_g = jnp.einsum("gecd,edf->gecf", buf, p["moe_wg"].astype(dt))
    h_u = jnp.einsum("gecd,edf->gecf", buf, p["moe_wu"].astype(dt))
    h = activation(h_g, cfg.act) * h_u
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["moe_wd"].astype(dt))
    out_buf = _moe_sharding_hint(out_buf)

    def combine_group(ob, m):
        ss, slot_e, slot_c, sg, keep = m
        vals = ob[slot_e, jnp.minimum(slot_c, C - 1)]  # (M, D)
        vals = jnp.where(keep[:, None], vals, 0) * sg[:, None].astype(dt)
        out = jnp.zeros((S, D), dt).at[ss].add(vals)
        return out

    out = jax.vmap(combine_group)(out_buf, meta)
    return out, aux.astype(jnp.float32)


# The sharding hint is monkeypatchable: the training step installs a
# mesh-aware constraint; standalone (single-device) use keeps identity.
def _identity(x):
    return x


_moe_sharding_hint = _identity
_moe_impl = "scatter"  # scatter | shard_map
_moe_mesh = None
_moe_dp_axes = ("data",)


def set_moe_sharding_hint(fn) -> None:
    global _moe_sharding_hint
    _moe_sharding_hint = fn if fn is not None else _identity


def set_moe_impl(impl: str, mesh=None, dp_axes=("data",)) -> None:
    global _moe_impl, _moe_mesh, _moe_dp_axes
    assert impl in ("scatter", "shard_map"), impl
    _moe_impl = impl
    _moe_mesh = mesh
    _moe_dp_axes = tuple(dp_axes)


# ===========================================================================
# shard_map MoE: explicit all-to-all dispatch (the TPU-canonical form)
# ===========================================================================
def apply_moe_shardmap(p, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE under shard_map: each data shard routes its
    local tokens into an (E, C_local, D) buffer with *local* scatters,
    exchanges expert shards with one ``all_to_all`` over the model axis,
    runs the expert FFN on local expert weights, and reverses.  Autodiff
    transposes the a2a to a2a — collectives stay all-to-all in the
    backward pass too (the scatter formulation degenerates to giant
    all-reduces under GSPMD; see EXPERIMENTS.md §Perf qwen3-moe).

    The local expert compute `(E_loc, C·m, D) × (E_loc, D, F)` is exactly
    the layout `kernels/moe_gmm.py` serves on TPU.
    """
    from jax.sharding import PartitionSpec as P

    mesh = _moe_mesh
    assert mesh is not None, "shard_map MoE needs set_moe_impl(mesh=...)"
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    dp = tuple(a for a in _moe_dp_axes if a in mesh.shape)
    m_size = mesh.shape.get("model", 1)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    assert E % m_size == 0, (E, m_size)
    assert S % m_size == 0, (S, m_size)
    # tokens split over BOTH data (batch) and model (sequence) axes, so the
    # expert FFN work is divided m_size ways (no redundant compute)
    local_tokens = (B // max(dp_size, 1)) * (S // m_size)
    C = max(int(local_tokens * K * cfg.moe_capacity_factor / E), K)
    C = ((C + 7) // 8) * 8  # pad for clean a2a tiling
    dt = x.dtype

    def local_fn(xl, router, wg, wu, wd):
        # xl: (B_loc, S_loc, D); router: (D, E); w*: (E_loc, D, F)
        b_loc, s_loc = xl.shape[0], xl.shape[1]
        toks = xl.reshape(b_loc * s_loc, D)
        logits = jnp.einsum("td,de->te", toks.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
        density = jnp.mean(jax.nn.one_hot(expert_ids[..., 0], E), axis=0)
        density_prob = jnp.mean(probs, axis=0)
        aux = jnp.sum(density * density_prob) * E * cfg.router_aux_weight

        M = toks.shape[0] * K
        flat_e = expert_ids.reshape(M)
        flat_g = gate_vals.reshape(M)
        src = jnp.repeat(jnp.arange(toks.shape[0]), K)
        order = jnp.argsort(flat_e)
        se, ss, sg = flat_e[order], src[order], flat_g[order]
        starts = jnp.searchsorted(se, jnp.arange(E), side="left")
        pos = jnp.arange(M) - starts[se]
        keep = pos < C
        slot_e = jnp.where(keep, se, 0)
        slot_c = jnp.where(keep, pos, C)
        buf = jnp.zeros((E, C + 1, D), dt)
        buf = buf.at[slot_e, slot_c].add(jnp.where(keep[:, None], toks[ss], 0))
        buf = buf[:, :C]  # (E, C, D) — all local so far

        # exchange: split E across the model axis, gather others' capacity
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)  # (E/m, C*m, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))) \
            * jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd.astype(dt))
        out_buf = jax.lax.all_to_all(out_buf, "model", split_axis=1,
                                     concat_axis=0, tiled=True)  # (E, C, D)

        vals = out_buf[slot_e, jnp.minimum(slot_c, C - 1)]
        vals = jnp.where(keep[:, None], vals, 0) * sg[:, None].astype(dt)
        out = jnp.zeros((toks.shape[0], D), dt).at[ss].add(vals)
        aux = jax.lax.pmean(aux, "model")
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return out.reshape(b_loc, s_loc, D), aux

    bspec = P(dp or None, "model", None)  # batch over data, seq over model
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(bspec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, p["router"], p["moe_wg"], p["moe_wu"], p["moe_wd"])
    return out, aux.astype(jnp.float32)
