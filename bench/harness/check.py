"""The comparison that decides ``correct``, against the plain reference.

Serving: a sample of the requests the window finished, drawn from the
seed and holding the longest, is run through the float32 reference
once (prompt followed by the served tokens); the number compared is the
widest gap by which a served token's logit lies below the reference's
best at that position.  Prefill's first token and every token decoded
through the paged cache are covered.

Training: the program's first ``checked_steps`` steps against the
reference's, from the same weights and batches: each step's loss, each
leaf's norm of the first gradient as the optimizer got it (clipped),
and each leaf's norm of the parameters' change after the checked steps.
Norms are compared as the gap between the two norms over the larger of
the reference's norm of that leaf and of the median leaf: the gradient
by the worst leaf (``grad_norm_gap``), the change by the worst leaf
(``change_norm_gap``: a leaf left unmoved or moved twice reads about 1)
and by the median leaf (``change_median_gap``).  The worst leaf's change
is the key bias on every seed: its gradient is near nought under
softmax (only the rotary position makes it differ between keys), so
Adam's ``g / (|g| + eps)`` moves much of it by round-off and its gap
swings fivefold between seeds, as wide as the control's; the median
leaf's is steady and separates the control.  Leaves whose
reference gradient is under a thousandth of the median leaf's move by
round-off alone under Adam and are left out of the change.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import traffic as T
from bench.harness import weights as W
from bench.harness.spec import reference
from bench.harness.train import leaf_norms
from bench.harness.weights import model_items

QUIET_GRAD = 1e-3


# ------------------------------------------------------------------ serving
def sample(finished: Sequence[Tuple[int, np.ndarray, List[int]]], seed: int,
           min_tokens: int, max_requests: int):
    """The longest finished request, then others in an order drawn from
    the seed, until ``min_tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    fin = sorted(finished, key=lambda r: r[0])
    longest = max(fin, key=lambda r: (len(r[2]), len(r[1]), -r[0]))
    rest = [r for r in fin if r[0] != longest[0]]
    order = np.random.default_rng([int(seed) % (1 << 64), 7]).permutation(
        len(rest))
    out, n = [longest], len(longest[2])
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i][2])
    return out


def _pad_len(total: int, block: int) -> int:
    return -(-total // block) * block


def served_gaps(params, config: Dict[str, Any], samples, pad_to: int,
                max_out: int, block: int, control: bool = False
                ) -> Dict[str, Any]:
    """Per sampled request the widest gap of a served token below the
    reference's best logit; with ``control`` also the widest gap of the
    token the fp8 control puts first."""
    ref = reference(config)
    items = model_items(config["model"])
    L = _pad_len(pad_to, block)
    out = {"requests": [], "served_gap": 0.0, "tokens": 0}
    if control:
        out["control_gap"] = 0.0
    for uid, prompt, served in samples:
        n = len(served)
        seq = np.zeros(L, np.int32)
        full = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(served[:-1], np.int32)])
        seq[:len(full)] = full
        pos = np.zeros(max_out, np.int32)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        tok = np.zeros(max_out, np.int32)
        tok[:n] = served
        valid = np.arange(max_out) < n
        gap, lg = ref.served_gaps(params, jnp.asarray(seq), jnp.asarray(pos),
                                  jnp.asarray(tok), jnp.asarray(valid),
                                  model_items=items, block=block)
        g = float(jnp.max(gap))
        row = {"uid": int(uid), "plen": int(len(prompt)), "served": n,
               "gap": g}
        if control:
            first = ref.control_first(params, jnp.asarray(seq),
                                      jnp.asarray(pos), model_items=items,
                                      block=block)
            best = jnp.max(lg, axis=-1)
            cg = jnp.where(jnp.asarray(valid), best - jnp.take_along_axis(
                lg, first[:, None], axis=-1)[:, 0], 0.0)
            row["control_gap"] = float(jnp.max(cg))
            out["control_gap"] = max(out["control_gap"], row["control_gap"])
        del lg
        out["requests"].append(row)
        out["served_gap"] = max(out["served_gap"], g)
        out["tokens"] += n
    return out


# ----------------------------------------------------------------- training
def train_reference(config: Dict[str, Any], mix: Dict[str, Any],
                    opt: Dict[str, Any], seed: int, steps: int,
                    precision: str = "f32", fault: Optional[str] = None
                    ) -> Dict[str, Any]:
    """The reference's first ``steps`` steps from the seed's weights on
    the seed's batches.  ``fault="half_batch"`` takes the mean over the
    first half of each batch's rows only."""
    ref = reference(config)
    model = config["model"]
    items = model_items(model)
    params = W.make_params(config, seed)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms, change = [], None, None
    b1, b2 = opt["betas"]
    for i in range(steps):
        rows = jnp.asarray(T.train_batch(mix, model["vocab_size"], seed, i))
        if fault == "half_batch":
            rows = rows[: rows.shape[0] // 2]
        loss, grads = ref.loss_and_grads(params, items, rows, precision)
        losses.append(float(loss))
        params, m, v, clipped = ref.adamw(
            params, m, v, grads, jnp.int32(i + 1), ref.lr_at(opt, i + 1),
            b1=b1, b2=b2, eps=opt["eps"], wd=opt["weight_decay"],
            clip=opt["clip_norm"])
        if i == 0:
            grad_norms = np.asarray(leaf_norms(clipped))
        del grads, clipped
    p0 = W.make_params(config, seed)
    change = np.asarray(jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a - b)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p0))]))
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


def norm_gaps(prog: np.ndarray, ref: np.ndarray,
              keep: Optional[np.ndarray] = None) -> Tuple[float, int, np.ndarray]:
    """(worst gap, its leaf index, all gaps): ``|prog - ref|`` over the
    larger of ``ref`` and the median of ``ref``."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    denom = np.maximum(ref, np.median(ref))
    gaps = np.abs(prog - ref) / denom
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i, gaps


def compare_train(prog: Dict[str, Any], ref: Dict[str, Any],
                  names: List[str]) -> Dict[str, Any]:
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    g, gi, ggaps = norm_gaps(prog["grad_norms"], ref["grad_norms"])
    keep = ref["grad_norms"] >= QUIET_GRAD * np.median(ref["grad_norms"])
    c, ci, cgaps = norm_gaps(prog["change_norms"], ref["change_norms"], keep)
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": g, "grad_worst_leaf": names[gi],
        "change_norm_gap": c, "change_worst_leaf": names[ci],
        "change_median_gap": float(np.median(cgaps[keep])),
        "left_out": [n for n, k in zip(names, keep) if not k],
        "per_leaf": {n: {"grad": float(a), "change": float(b)}
                     for n, a, b in zip(names, ggaps, cgaps)},
    }
