"""Activation-sharding hints: installable with_sharding_constraint hooks.

Models are mesh-agnostic; they call ``hints.act(x)`` on block inputs and
``hints.logits(x)`` on the LM head output.  The step factory installs
mesh-aware constraints before tracing (and clears them after).  Without
installed hints both are identity — single-device paths are unaffected.

Why this exists: with fully auto sharding propagation, XLA occasionally
picks partial-sum strategies that replicate the batch inside the layer
scan (observed: 20 TB all-reduced attention scores on the 16×16 mesh).
Pinning just the block boundary (batch → dp axes) and the logits (vocab →
model axis) keeps propagation honest everywhere in between.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACT: Optional[Callable] = None
_LOGITS: Optional[Callable] = None
_ATTN_Q: Optional[Callable] = None
_PIN: Optional[Callable] = None
_MESH: Optional[Tuple[Mesh, Optional[Tuple[str, ...]]]] = None  # (mesh, dp)


def act(x):
    """Constrain a (batch, seq, embed) activation."""
    return _ACT(x) if _ACT is not None else x


def logits(x):
    """Constrain a (batch, seq, vocab) logits tensor."""
    return _LOGITS(x) if _LOGITS is not None else x


def pin_replicated(x):
    """Pin a tensor fully replicated at a use site (escape hatch for
    GSPMD propagation pathologies, e.g. tied-embedding logits matmuls
    resharding the gather operand)."""
    return _PIN(x) if _PIN is not None else x


def attn_q(x):
    """Optionally shard attention queries on the sequence dim over the
    model axis (context parallelism) — the fix for archs whose head count
    does not divide the model axis (attention would otherwise replicate)."""
    return _ATTN_Q(x) if _ATTN_Q is not None else x


def shard_batch(fn: Callable) -> Callable:
    """``fn`` run per data shard when a mesh is installed.

    GSPMD cannot partition a Pallas (Mosaic) kernel, so a kernel call
    whose arguments and result all lead with the batch dimension runs
    under ``shard_map``: batch split over the dp axes when it divides
    them, else replicated.  Identity without an installed mesh."""
    if _MESH is None:
        return fn
    mesh, dp = _MESH

    def per_shard(*args):
        split = dp and args[0].shape[0] % _size(mesh, dp) == 0
        spec = P(dp) if split else P()
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * len(args),
                             out_specs=spec, check_vma=False)(*args)

    return per_shard


def install(mesh: Mesh, dp_axes=("data",), model_axes=("model",),
            vocab_on_model: bool = True, seq_shard_attn: bool = False) -> None:
    global _ACT, _LOGITS, _ATTN_Q, _PIN, _MESH
    dp = tuple(a for a in dp_axes if a in mesh.shape) or None
    mdl = tuple(a for a in model_axes if a in mesh.shape) or None

    def _act(x):
        if x.ndim < 2:
            return x
        spec = P(dp, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def _logits(x):
        if x.ndim != 3:
            return x
        v = x.shape[-1]
        vm = mdl if (vocab_on_model and mdl and v % _size(mesh, mdl) == 0) else None
        b = dp if (dp and x.shape[0] % _size(mesh, dp) == 0) else None
        spec = P(b, None, vm)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def _attn_q(x):
        # (B, S, H, D): batch -> dp, seq -> model
        if x.ndim != 4 or not mdl:
            return x
        s_ = x.shape[1]
        if s_ % _size(mesh, mdl) != 0 or s_ < 2 * _size(mesh, mdl):
            return x
        b = dp if (dp and x.shape[0] % _size(mesh, dp) == 0) else None
        spec = P(b, mdl, None, None)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def _pin(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*([None] * x.ndim))))

    _ACT, _LOGITS = _act, _logits
    _ATTN_Q = _attn_q if seq_shard_attn else None
    _PIN = _pin
    _MESH = (mesh, dp)


def _size(mesh: Mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def clear() -> None:
    global _ACT, _LOGITS, _ATTN_Q, _PIN, _MESH
    _ACT = _LOGITS = _ATTN_Q = _PIN = _MESH = None
