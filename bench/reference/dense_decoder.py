"""Plain reference of a dense decoder-only transformer, in float32.

Follows the configuration file: token embedding; per layer RMSNorm ->
grouped-query attention (optional q/k/v bias, rotary embedding on the
two halves of each head, causal softmax) -> residual -> RMSNorm ->
SwiGLU MLP -> residual; final RMSNorm; a tied or separate output head.
No kernels, no cache, no batching tricks.  Every matrix product runs at
``Precision.HIGHEST``, so a TPU computes it in float32.

``precision="fp8"`` is the control: every matrix-product operand is
rounded to float8 e4m3 with one scale per tensor (``amax / 448``; the
output head one scale per vocabulary slice) before the product, and its
gradient to float8 e5m2: the step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0        # largest finite float8 e4m3
E5M2_MAX = 57344.0    # largest finite float8 e5m2


def _scale(x: jax.Array, fmax: float = F8_MAX) -> jax.Array:
    return jnp.maximum(jnp.max(jnp.abs(jax.lax.stop_gradient(x))),
                       1e-30) / fmax


def _round(x: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 with one scale for the tensor; its
    gradient rounded to float8 e5m2 the same way, as float8 training
    does."""
    return _round(x, _scale(x), jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, ct):
    return (_round(ct, _scale(ct, E5M2_MAX), jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _q(x: jax.Array, precision: str) -> jax.Array:
    if precision == "f32":
        return x
    if precision != "fp8":
        raise ValueError(precision)
    return fp8(x)


def mm(eq: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    return jnp.einsum(eq, _q(a.astype(jnp.float32), precision),
                      _q(b.astype(jnp.float32), precision),
                      precision=HIGHEST)


def rms_norm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * g


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, heads, Dh); rotate the pair (x[i], x[i + Dh/2]) by
    ``pos * theta ** (-i / (Dh/2))``."""
    S, Dh = x.shape[1], x.shape[-1]
    half = Dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, precision: str, block: int) -> jax.Array:
    """Causal grouped-query attention; query head ``h`` reads key/value
    head ``h // (H / KH)``.  Queries go in blocks of ``block`` rows so
    the score matrix of a long sequence fits."""
    B, S, H, Dh = q.shape
    KH = k.shape[2]
    G = H // KH
    q = q.reshape(B, S, KH, G, Dh) * Dh ** -0.5
    kpos = jnp.arange(S)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = mm("bqkgd,btkd->bkgqt", qb, k, precision)
        qpos = start + jnp.arange(block)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bkgqt,btkd->bqkgd", p, v, precision)

    starts = jnp.arange(0, S, block)
    out = jax.lax.map(one, starts)                 # (nb, B, block, KH, G, Dh)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, Dh)
    return out


def hidden(params: Dict[str, Any], model: Dict[str, Any], tokens: jax.Array,
           precision: str = "f32", block: int = 0) -> jax.Array:
    """Final normed hidden states (B, S, D) for ``tokens`` (B, S)."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    S = tokens.shape[1]
    block = block or S
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    blocks = params["blocks"]

    def layer(x, p):
        h = rms_norm(x, p["norm1_g"], eps)
        q = mm("bsd,dhk->bshk", h, p["attn_wq"], precision)
        k = mm("bsd,dhk->bshk", h, p["attn_wk"], precision)
        v = mm("bsd,dhk->bshk", h, p["attn_wv"], precision)
        if model["qkv_bias"]:
            q, k, v = q + p["attn_bq"], k + p["attn_bk"], v + p["attn_bv"]
        a = attention(rope(q, theta), rope(k, theta), v, precision, block)
        x = x + mm("bshk,hkd->bsd", a, p["attn_wo"], precision)
        h = rms_norm(x, p["norm2_g"], eps)
        g = mm("bsd,df->bsf", h, p["mlp_wg"], precision)
        u = mm("bsd,df->bsf", h, p["mlp_wu"], precision)
        x = x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["mlp_wd"], precision)
        return x, None

    x, _ = jax.lax.scan(layer, x, blocks)
    return rms_norm(x, params["final_g"], eps)


def head(params: Dict[str, Any], model: Dict[str, Any]) -> jax.Array:
    """The output projection (D, V)."""
    return params["embed"].T if model["tie_embeddings"] else params["lm_head"]


def logits(params, model, x, precision: str = "f32",
           chunk: int = 16384) -> jax.Array:
    """``x @ head`` in slices of the vocabulary (under fp8 one scale per
    slice), so no full-size copy of the head is made."""
    w = head(params, model)
    V = w.shape[1]
    parts = [mm("...d,dv->...v", x, w[:, a:a + chunk], precision)
             for a in range(0, V, chunk)]
    return jnp.concatenate(parts, axis=-1)


def layout(model: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Leaf path -> (shape, init kind) the kind is ``gain``, ``bias`` or
    ``<variance>/<fan_in>`` (``bench/harness/weights.py``).  The tree is
    the one ``hidden`` reads: ``embed``, ``final_g``, optional
    ``lm_head``, and ``blocks`` of layer-stacked leaves."""
    L, D = model["num_layers"], model["d_model"]
    H, KH, Dh = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    F, V = model["d_ff"], model["vocab_size"]
    out = {
        "embed": ((V, D), f"1/{D}"),
        "final_g": ((D,), "gain"),
        "blocks/norm1_g": ((L, D), "gain"),
        "blocks/norm2_g": ((L, D), "gain"),
        "blocks/attn_wq": ((L, D, H, Dh), f"1/{D}"),
        "blocks/attn_wk": ((L, D, KH, Dh), f"1/{D}"),
        "blocks/attn_wv": ((L, D, KH, Dh), f"1/{D}"),
        "blocks/attn_wo": ((L, H, Dh, D), f"1/{H * Dh}"),
        "blocks/mlp_wg": ((L, D, F), f"1/{D}"),
        "blocks/mlp_wu": ((L, D, F), f"1/{D}"),
        "blocks/mlp_wd": ((L, F, D), f"1/{F}"),
    }
    if not model["tie_embeddings"]:
        out["lm_head"] = ((D, V), f"1/{D}")
    if model["qkv_bias"]:
        out["blocks/attn_bq"] = ((L, H, Dh), "bias")
        out["blocks/attn_bk"] = ((L, KH, Dh), "bias")
        out["blocks/attn_bv"] = ((L, KH, Dh), "bias")
    return out


# ---------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("model_items", "block"))
def served_gaps(params, tokens, positions, served, valid, *, model_items,
                block):
    """For one sequence (prompt followed by its served tokens, padded):
    at each ``positions[i]`` the gap ``max(logits) - logits[served[i]]``
    of the float32 reference, and the same gap for the token the fp8
    control would put first there.  Invalid entries read 0."""
    model = dict(model_items)
    x = hidden(params, model, tokens[None], "f32", block)[0]
    xs = x[positions]                                   # (n, D)
    ref = logits(params, model, xs, "f32")              # (n, V)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    gap = jnp.where(valid, best - got, 0.0)
    return gap, ref


@functools.partial(jax.jit, static_argnames=("model_items", "block"))
def control_first(params, tokens, positions, *, model_items, block):
    """The token the fp8 control puts first at each position."""
    model = dict(model_items)
    x = hidden(params, model, tokens[None], "fp8", block)[0]
    return jnp.argmax(logits(params, model, x[positions], "fp8"), axis=-1)


# ---------------------------------------------------------------- training
def row_nll_sum(params, model, row, precision: str) -> jax.Array:
    """Sum over the row's S - 1 next-token predictions of -log p."""
    x = hidden(params, model, row[None], precision)[0, :-1]
    lg = logits(params, model, x, precision)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold)


@functools.partial(jax.jit, static_argnames=("model_items", "precision"))
def row_grad(params, row, *, model_items, precision):
    model = dict(model_items)
    return jax.value_and_grad(row_nll_sum)(params, model, row, precision)


@jax.jit
def _accumulate(acc, g, scale):
    return jax.tree.map(lambda a, b: a + b * scale, acc, g)


def loss_and_grads(params, model_items, rows, precision: str = "f32"
                   ) -> Tuple[jax.Array, Any]:
    """Mean next-token loss over all rows and its gradient, one row at a
    time (the rows' sums added, then divided by the count)."""
    denom = rows.shape[0] * (rows.shape[1] - 1)
    total = jnp.zeros((), jnp.float32)
    grads = None
    for r in range(rows.shape[0]):
        nll, g = row_grad(params, rows[r], model_items=model_items,
                          precision=precision)
        total = total + nll
        grads = (jax.tree.map(lambda t: t / denom, g) if grads is None
                 else _accumulate(grads, g, 1.0 / denom))
    return total / denom, grads


def lr_at(opt: Dict[str, Any], count: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay
    to 0 at ``total_steps``."""
    import math
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((count - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + math.cos(math.pi * frac))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "clip"))
def adamw(params, m, v, grads, count, lr, *, b1, b2, eps, wd, clip):
    """One AdamW step: clip by the global norm, decoupled weight decay on
    matrices (ndim > 1) only.  Returns (params, m, v, clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    c = count.astype(jnp.float32)

    def one(p, m_, v_, g):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        upd = (m_ / (1 - b1 ** c)) / (jnp.sqrt(v_ / (1 - b2 ** c)) + eps)
        if p.ndim > 1:
            upd = upd + wd * p
        return p - lr * upd, m_, v_

    out = jax.tree.map(one, params, m, v, grads)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), grads
