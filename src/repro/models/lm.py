"""Decoder-only language models: dense, MoE, hybrid (hymba), VLM, xLSTM.

One implementation, four code paths:
  * ``forward_train`` — full-sequence causal forward (train_4k), scan over
    layers with selectable remat policy;
  * ``prefill``      — forward + KV/state cache emission (prefill_32k);
  * ``decode_step``  — one-token step against the cache (decode_32k /
    long_500k);
  * ``loss``         — next-token CE (+ MoE aux), f32 accumulation.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref as kref
from repro.models import recurrent as rec
from repro.models.attention import (
    attend_decode,
    attend_decode_paged,
    attend_train,
    attend_verify,
    attend_verify_paged,
    qkv,
    out_proj,
)
from repro.models.common import (
    ParamBuilder,
    activation,
    apply_norm,
    apply_rope,
    make_norm,
    rope_angles,
)
from repro.models.moe import apply_moe, init_moe
from repro.parallel import hints

Pytree = Any


# ===========================================================================
# Init
# ===========================================================================
def init_mlp(pb: ParamBuilder, cfg: ModelConfig, L: int):
    D, F = cfg.d_model, cfg.d_ff
    if cfg.act == "silu":
        pb.p("mlp_wg", (L, D, F), ("layers", "embed", "mlp"))
    pb.p("mlp_wu", (L, D, F), ("layers", "embed", "mlp"))
    pb.p("mlp_wd", (L, F, D), ("layers", "mlp", "embed"))


@jax.named_scope("lm.mlp")
def apply_mlp(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    hu = jnp.einsum("bsd,df->bsf", x, p["mlp_wu"].astype(dt))
    if cfg.act == "silu":
        hg = jnp.einsum("bsd,df->bsf", x, p["mlp_wg"].astype(dt))
        h = activation(hg, "silu") * hu
    else:
        h = activation(hu, "gelu")
    return jnp.einsum("bsf,fd->bsd", h, p["mlp_wd"].astype(dt))


def _init_decoder_blocks(pb: ParamBuilder, cfg: ModelConfig):
    from repro.models.attention import init_attention

    L, D = cfg.num_layers, cfg.d_model
    g = (2 if cfg.norm == "layernorm" else 1)
    pb.p("norm1_g", (L, D), ("layers", "embed"), init="ones")
    pb.p("norm2_g", (L, D), ("layers", "embed"), init="ones")
    if cfg.norm == "layernorm":
        pb.p("norm1_b", (L, D), ("layers", "embed"), init="zeros")
        pb.p("norm2_b", (L, D), ("layers", "embed"), init="zeros")
    init_attention(pb, cfg, L)
    if cfg.family == "hybrid":
        rec.init_ssm(pb, cfg, L)
        pb.p("fuse_attn", (L, D), ("layers", "embed"), init="ones")
        pb.p("fuse_ssm", (L, D), ("layers", "embed"), init="ones")
    if cfg.num_experts > 0:
        init_moe(pb, cfg, L)
    elif cfg.d_ff > 0:
        init_mlp(pb, cfg, L)


def _init_xlstm_blocks(pb: ParamBuilder, cfg: ModelConfig):
    """Grouped layout: G groups of (slstm_every - 1) mLSTM + 1 sLSTM."""
    every = cfg.slstm_every
    if every:
        assert cfg.num_layers % every == 0, (cfg.num_layers, every)
        groups = cfg.num_layers // every
        m_inner = every - 1
        mb = pb.child("mlstm")
        rec.init_mlstm(mb, cfg, groups * m_inner)
        sb = pb.child("slstm")
        rec.init_slstm(sb, cfg, groups)
    else:
        mb = pb.child("mlstm")
        rec.init_mlstm(mb, cfg, cfg.num_layers)


def init_lm(cfg: ModelConfig, rng: jax.Array) -> Tuple[Pytree, Pytree]:
    pb = ParamBuilder(rng)
    pb.p("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"))
    if not cfg.tie_embeddings:
        pb.p("lm_head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    make_norm(pb, "final", cfg.d_model, cfg.norm)
    blocks = pb.child("blocks")
    if cfg.family == "ssm":
        _init_xlstm_blocks(blocks, cfg)
    else:
        _init_decoder_blocks(blocks, cfg)
    return pb.params, pb.axes


# ===========================================================================
# Shared pieces
# ===========================================================================
# Device scopes of the dense decoder (``lm.embed``, ``lm.attn``,
# ``lm.mlp``, ``lm.head``) name its parts in a profile's op metadata;
# they cost nothing at run time.
@jax.named_scope("lm.embed")
def embed_tokens(params: Pytree, cfg: ModelConfig, tokens: jax.Array,
                 extra: Optional[Dict[str, jax.Array]] = None) -> jax.Array:
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    if cfg.family == "vlm" and extra is not None and "image_embeds" in extra:
        n_img = extra["image_embeds"].shape[1]
        img = extra["image_embeds"].astype(dt)
        if tokens.shape[1] >= n_img:
            x = jax.lax.dynamic_update_slice(x, img, (0, 0, 0))
    return x


@jax.named_scope("lm.head")
def lm_logits(params: Pytree, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    xn = apply_norm(params, "final", x, cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if cfg.tie_embeddings:
        # GSPMD may otherwise reshard the shared table for this matmul and
        # break the token-gather partitioning (observed on whisper/hymba)
        head = hints.pin_replicated(head)
    return hints.logits(jnp.einsum("bsd,dv->bsv", xn, head.astype(xn.dtype)))


def _layer_flags(cfg: ModelConfig) -> jax.Array:
    """Per-layer flag: 1 = global attention, 0 = sliding window."""
    if cfg.family == "hybrid" and cfg.sliding_window > 0:
        flags = jnp.zeros((cfg.num_layers,), jnp.int32)
        for i in cfg.global_attn_layers:
            flags = flags.at[i].set(1)
        return flags
    return jnp.ones((cfg.num_layers,), jnp.int32)


def _block_train(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                 flag: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One decoder block (train path). Returns (x, aux_loss)."""
    h = apply_norm(p, "norm1", x, cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "hybrid":
        attn_out = jax.lax.cond(
            flag > 0,
            lambda hh: attend_train(p, hh, cfg, causal=True, window=0),
            lambda hh: attend_train(p, hh, cfg, causal=True, window=cfg.sliding_window),
            h,
        )
        ssm_out = rec.apply_ssm(p, h, cfg)
        mix = 0.5 * (
            attn_out * p["fuse_attn"].astype(x.dtype)
            + ssm_out * p["fuse_ssm"].astype(x.dtype)
        )
        x = x + mix
    else:
        with jax.named_scope("lm.attn"):
            x = x + attend_train(p, h, cfg, causal=True)
    h2 = apply_norm(p, "norm2", x, cfg.norm)
    if cfg.num_experts > 0:
        out, aux = apply_moe(p, h2, cfg)
        x = x + out
    elif cfg.d_ff > 0:
        x = x + apply_mlp(p, h2, cfg)
    return x, aux


def _scan_blocks(cfg: ModelConfig, blocks: Pytree, x: jax.Array,
                 remat: str = "none") -> Tuple[jax.Array, jax.Array]:
    flags = _layer_flags(cfg)

    def body(carry, xs):
        pl_, fl = xs
        xx, aux_acc = carry
        xx = hints.act(xx)
        xx, aux = _block_train(cfg, pl_, xx, fl)
        return (xx, aux_acc + aux), None

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), (blocks, flags))
    return x, aux


def _xlstm_forward(cfg: ModelConfig, blocks: Pytree, x: jax.Array,
                   remat: str = "none") -> jax.Array:
    every = cfg.slstm_every

    if not every:
        def mbody(xx, pl_):
            return rec.apply_mlstm(pl_, xx, cfg), None
        if remat in ("full", "dots"):
            mbody = jax.checkpoint(mbody)
        x, _ = jax.lax.scan(mbody, x, blocks["mlstm"])
        return x

    groups = cfg.num_layers // every
    m_inner = every - 1
    mparams = jax.tree.map(
        lambda a: a.reshape((groups, m_inner) + a.shape[1:]), blocks["mlstm"]
    )

    def gbody(xx, xs):
        mp, sp = xs

        def mbody(xxx, pl_):
            return rec.apply_mlstm(pl_, xxx, cfg), None

        xx, _ = jax.lax.scan(mbody, xx, mp)
        xx = rec.apply_slstm(sp, xx, cfg)
        return xx, None

    if remat in ("full", "dots"):
        gbody = jax.checkpoint(gbody)
    x, _ = jax.lax.scan(gbody, x, (mparams, blocks["slstm"]))
    return x


def forward_train(params: Pytree, cfg: ModelConfig, tokens: jax.Array,
                  extra: Optional[Dict[str, jax.Array]] = None,
                  remat: str = "none") -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) -> (logits (B,S,V), aux_loss)."""
    x = hints.act(embed_tokens(params, cfg, tokens, extra))
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        x = _xlstm_forward(cfg, params["blocks"], x, remat)
    else:
        x, aux = _scan_blocks(cfg, params["blocks"], x, remat)
    return lm_logits(params, cfg, x), aux


def loss_fn(params: Pytree, cfg: ModelConfig, batch: Dict[str, jax.Array],
            remat: str = "none") -> Tuple[jax.Array, Dict[str, jax.Array]]:
    tokens = batch["tokens"]
    logits, aux = forward_train(params, cfg, tokens, batch, remat)
    logits = logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold  # (B, S-1)
    mask = jnp.ones_like(nll)
    if cfg.family == "vlm" and cfg.num_image_tokens:
        pos = jnp.arange(nll.shape[1])[None]
        mask = (pos >= cfg.num_image_tokens - 1).astype(nll.dtype) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum(nll * mask) / denom
    total = ce + aux
    return total, {"loss": total, "ce": ce, "aux": aux,
                   "tokens": denom.astype(jnp.float32)}


# ===========================================================================
# Prefill / decode
# ===========================================================================
def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Pytree:
    """Zero cache pytree for decode-only lowering (decode_32k / long_500k)."""
    dt = jnp.dtype(cfg.dtype)
    KH, Dh, L = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    if cfg.family == "ssm":
        every = cfg.slstm_every
        if every:
            groups = L // every
            m_inner = every - 1
            m = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (groups, m_inner) + a.shape),
                rec.mlstm_state_spec(cfg, batch),
            )
            s = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (groups,) + a.shape),
                rec.slstm_state_spec(cfg, batch),
            )
            return {"mlstm": m, "slstm": s, "pos": jnp.zeros((batch,), jnp.int32)}
        m = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape),
            rec.mlstm_state_spec(cfg, batch),
        )
        return {"mlstm": m, "pos": jnp.zeros((batch,), jnp.int32)}

    if cfg.family == "hybrid":
        layers = []
        W = cfg.sliding_window
        for i in range(L):
            is_global = i in cfg.global_attn_layers
            size = max_seq if is_global else min(W, max_seq)
            layers.append({
                "k": jnp.zeros((batch, size, KH, Dh), dt),
                "v": jnp.zeros((batch, size, KH, Dh), dt),
                "slot_pos": jnp.full((batch, size), -1, jnp.int32),
                "ssm": rec.ssm_state_spec(cfg, batch),
            })
        return {"layers": layers, "pos": jnp.zeros((batch,), jnp.int32)}

    return {
        "k": jnp.zeros((L, batch, max_seq, KH, Dh), dt),
        "v": jnp.zeros((L, batch, max_seq, KH, Dh), dt),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def supports_paged_cache(cfg: ModelConfig) -> bool:
    """Families whose decode cache is the plain dense ``{k, v, pos}``
    pytree can be paged: K/V at position t is a pure function of tokens
    ``<= t``, so pages are relocatable and prompt-prefix pages are
    shareable.  Recurrent/hybrid state and the encoder-decoder cross
    cache have no per-position pages to relocate."""
    return not cfg.is_encoder_decoder and cfg.family not in ("ssm", "hybrid")


def init_paged_cache(cfg: ModelConfig, batch: int, num_pages: int,
                     page_size: int, max_pages: int) -> Pytree:
    """Paged decode cache: one global KV pool shared by all slots plus a
    per-slot page table.  Pool layout is ``(L, KH, num_pages, page, Dh)``
    — KV-head-major so the Pallas kernel's page blocks are
    ``(page, Dh)`` tiles.  ``page_table[b, j] = -1`` marks an unmapped
    logical page; pool page 0 is reserved by the engine as the null
    (parking) page and never allocated."""
    if not supports_paged_cache(cfg):
        raise ValueError(
            f"paged KV cache unsupported for family {cfg.family!r}"
            f"{' (encoder-decoder)' if cfg.is_encoder_decoder else ''}: "
            f"only dense-attention caches page"
        )
    dt = jnp.dtype(cfg.dtype)
    KH, Dh, L = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    return {
        "k_pool": jnp.zeros((L, KH, num_pages, page_size, Dh), dt),
        "v_pool": jnp.zeros((L, KH, num_pages, page_size, Dh), dt),
        "page_table": jnp.full((batch, max_pages), -1, jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params: Pytree, cfg: ModelConfig, tokens: jax.Array,
            extra: Optional[Dict[str, jax.Array]] = None,
            max_seq: Optional[int] = None,
            lens: Optional[jax.Array] = None) -> Tuple[jax.Array, Pytree]:
    """Full forward emitting the cache. Returns (last-token logits, cache).

    ``lens`` (B,) int32 marks ragged rows in a right-padded batch: logits
    come from position ``lens[b] - 1`` and the cache position is set to
    ``lens[b]``, so decode's ``kv_len`` masking hides the pad-position
    K/V garbage.  Attention-only models qualify (causality makes every
    real position independent of the right padding); recurrent families
    would carry pad steps in their state, so they reject ``lens``."""
    B, S = tokens.shape
    max_seq = max_seq or S
    if lens is not None and cfg.family in ("ssm", "hybrid"):
        raise ValueError(f"padded prefill (lens) unsupported for family "
                         f"{cfg.family!r}: recurrent state would include "
                         f"pad steps")
    if lens is not None and cfg.num_experts > 0:
        raise ValueError("padded prefill (lens) unsupported for MoE: "
                         "expert capacity scales with the padded length "
                         "and pad tokens would evict real ones")
    x = embed_tokens(params, cfg, tokens, extra)
    blocks = params["blocks"]

    if cfg.family == "ssm":
        cache = _xlstm_prefill_cache(cfg, blocks, x)
        xout = cache.pop("_x")
        logits = lm_logits(params, cfg, xout[:, -1:])
        cache["pos"] = jnp.full((B,), S, jnp.int32)
        return logits[:, 0], cache

    if cfg.family == "hybrid":
        cache_layers = []
        flags = [int(i in cfg.global_attn_layers) for i in range(cfg.num_layers)]
        for i in range(cfg.num_layers):
            pl_ = jax.tree.map(lambda a: a[i], blocks)
            x, cl = _hybrid_block_prefill(cfg, pl_, x, bool(flags[i]), max_seq)
            cache_layers.append(cl)
        logits = lm_logits(params, cfg, x[:, -1:])
        cache = {"layers": cache_layers, "pos": jnp.full((B,), S, jnp.int32)}
        return logits[:, 0], cache

    flags = _layer_flags(cfg)
    cos, sin = rope_angles(jnp.arange(S), cfg.head_dim, cfg.rope_theta)

    def body(xx, xs):
        pl_, fl = xs
        xx = hints.act(xx)
        h = apply_norm(pl_, "norm1", xx, cfg.norm)
        with jax.named_scope("lm.attn"):
            q, k, v = qkv(pl_, h, cfg)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            attn = ops.flash_attention(q, k, v, causal=True)
            xx = xx + out_proj(pl_, attn)
        h2 = apply_norm(pl_, "norm2", xx, cfg.norm)
        if cfg.num_experts > 0:
            out, _ = apply_moe(pl_, h2, cfg)
            xx = xx + out
        elif cfg.d_ff > 0:
            xx = xx + apply_mlp(pl_, h2, cfg)
        pad = max_seq - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return xx, (kc, vc)

    x, (kcache, vcache) = jax.lax.scan(body, x, (blocks, flags))
    if lens is None:
        x_last = x[:, -1:]
        pos = jnp.full((B,), S, jnp.int32)
    else:
        lens = lens.astype(jnp.int32)
        x_last = x[jnp.arange(B), lens - 1][:, None]
        pos = lens
    logits = lm_logits(params, cfg, x_last)
    cache = {"k": kcache, "v": vcache, "pos": pos}
    return logits[:, 0], cache


def _xlstm_prefill_cache(cfg, blocks, x):
    every = cfg.slstm_every
    B = x.shape[0]
    if every:
        groups = cfg.num_layers // every
        m_inner = every - 1
        mparams = jax.tree.map(
            lambda a: a.reshape((groups, m_inner) + a.shape[1:]), blocks["mlstm"]
        )
        m_states, s_states = [], []
        for g in range(groups):
            ms = []
            for j in range(m_inner):
                pl_ = jax.tree.map(lambda a: a[g][j], mparams)
                x, st = _mlstm_prefill_layer(pl_, x, cfg)
                ms.append(st)
            m_states.append(jax.tree.map(lambda *a: jnp.stack(a), *ms))
            sp = jax.tree.map(lambda a: a[g], blocks["slstm"])
            x, st = _slstm_prefill_layer(sp, x, cfg)
            s_states.append(st)
        m = jax.tree.map(lambda *a: jnp.stack(a), *m_states)
        s = jax.tree.map(lambda *a: jnp.stack(a), *s_states)
        return {"mlstm": m, "slstm": s, "_x": x}
    states = []
    for l in range(cfg.num_layers):
        pl_ = jax.tree.map(lambda a: a[l], blocks["mlstm"])
        x, st = _mlstm_prefill_layer(pl_, x, cfg)
        states.append(st)
    return {"mlstm": jax.tree.map(lambda *a: jnp.stack(a), *states), "_x": x}


def _mlstm_prefill_layer(p, x, cfg):
    from repro.models.common import layer_norm

    d_in, NH, DH = rec.mlstm_dims(cfg)
    B, S, D = x.shape
    xn = layer_norm(x, p["ln_g"], p["ln_b"])
    h = jnp.einsum("bsd,de->bse", xn, p["w_up_x"].astype(x.dtype))
    z = jnp.einsum("bsd,de->bse", xn, p["w_up_z"].astype(x.dtype))
    q, k, v, i_pre, f_pre = rec._mlstm_qkvif(p, h, cfg)
    hv, (C, n, m) = kref.mlstm_scan(q, k, v, i_pre, f_pre)  # (B,NH,S,DH)
    from repro.models.common import rms_norm as _rms
    out = _rms(hv.transpose(0, 2, 1, 3), p["headnorm_g"])
    out = out.reshape(B, S, d_in) * jax.nn.silu(z)
    x = x + jnp.einsum("bse,ed->bsd", out, p["w_down"].astype(x.dtype))
    return x, {"C": C, "n": n, "m": m}


def _slstm_prefill_layer(p, x, cfg):
    from repro.models.common import layer_norm
    from repro.models.common import rms_norm as _rms

    B, S, D = x.shape
    NH, DH = rec.slstm_dims(cfg)
    xn = layer_norm(x, p["ln_g"], p["ln_b"]).astype(jnp.float32)

    def step(state, xt):
        new = rec._slstm_cell(p, state, xt)
        return new, new["h"]

    state0 = rec.slstm_state_spec(cfg, B)
    state, hs = jax.lax.scan(step, state0, xn.transpose(1, 0, 2))
    hs = hs.transpose(1, 0, 2, 3)
    out = _rms(hs, p["headnorm_g"]).reshape(B, S, D).astype(x.dtype)
    x = x + out
    xn2 = apply_norm(p, "ln2", x, "layernorm")
    hg = jnp.einsum("bsd,df->bsf", xn2, p["ffn_wg"].astype(x.dtype))
    hu = jnp.einsum("bsd,df->bsf", xn2, p["ffn_wu"].astype(x.dtype))
    ff = jnp.einsum("bsf,fd->bsd", activation(hg, "gelu") * hu, p["ffn_wd"].astype(x.dtype))
    return x + ff, state


def _hybrid_block_prefill(cfg, p, x, is_global: bool, max_seq: int):
    B, S, D = x.shape
    W = cfg.sliding_window
    h = apply_norm(p, "norm1", x, cfg.norm)
    q, k, v = qkv(p, h, cfg)
    cos, sin = rope_angles(jnp.arange(S), cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    attn = ops.flash_attention(q, k, v, causal=True, window=0 if is_global else W)
    attn_out = out_proj(p, attn)

    # ssm branch with state capture
    xin, z = rec._ssm_proj(p, h, cfg, "ssm")
    K = cfg.ssm_conv
    conv_w = p["ssm_conv_w"].astype(xin.dtype)
    xpad = jnp.pad(xin, ((0, 0), (K - 1, 0), (0, 0)))
    xc = sum(xpad[:, i: i + S] * conv_w[i][None, None] for i in range(K))
    xc = jax.nn.silu(xc)
    dt, A, Bm, Cm = rec._ssm_coeffs(p, xc, cfg, "ssm")
    y, hstate = ops.ssm_scan_with_state(xc, dt.astype(xc.dtype), A, Bm, Cm, p["ssm_D"])
    y = y * jax.nn.silu(z)
    ssm_out = jnp.einsum("bse,ed->bsd", y, p["ssm_w_out"].astype(x.dtype))

    mix = 0.5 * (attn_out * p["fuse_attn"].astype(x.dtype)
                 + ssm_out * p["fuse_ssm"].astype(x.dtype))
    x = x + mix
    h2 = apply_norm(p, "norm2", x, cfg.norm)
    x = x + apply_mlp(p, h2, cfg)

    # cache entry
    size = max_seq if is_global else min(W, max_seq)
    if size >= S:
        kc = jnp.pad(k, ((0, 0), (0, size - S), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, size - S), (0, 0), (0, 0)))
        sp = jnp.pad(jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
                     ((0, 0), (0, size - S)), constant_values=-1)
    else:  # ring layout: slot j holds pos p ≡ j (mod size), p in [S-size, S)
        j = jnp.arange(size)
        pos_of_slot = S - size + ((j - (S - size)) % size)
        kc = k[:, pos_of_slot]
        vc = v[:, pos_of_slot]
        sp = jnp.broadcast_to(pos_of_slot[None], (B, size))
    conv_state = xin[:, S - (K - 1): S]  # last K-1 raw inputs
    return x, {
        "k": kc, "v": vc, "slot_pos": sp.astype(jnp.int32),
        "ssm": {"h": hstate, "conv": conv_state.astype(jnp.float32)},
    }


def decode_step(params: Pytree, cfg: ModelConfig, cache: Pytree,
                tokens: jax.Array) -> Tuple[jax.Array, Pytree]:
    """tokens: (B, 1). Returns (logits (B, V), new cache).

    Dispatches on the cache layout: a ``k_pool`` key marks the paged
    cache (:func:`init_paged_cache`) and routes through
    :func:`repro.models.attention.attend_decode_paged`; otherwise the
    dense per-slot cache paths run unchanged."""
    pos = cache["pos"]  # (B,)
    x = embed_tokens(params, cfg, tokens)
    blocks = params["blocks"]

    if "k_pool" in cache:
        page_table = cache["page_table"]

        def body(xx, xs):
            pl_, kp, vp = xs
            xx = hints.act(xx)
            h = apply_norm(pl_, "norm1", xx, cfg.norm)
            with jax.named_scope("lm.attn"):
                attn_out, nkp, nvp = attend_decode_paged(
                    pl_, h, kp, vp, page_table, pos, cfg
                )
            xx = xx + attn_out
            h2 = apply_norm(pl_, "norm2", xx, cfg.norm)
            if cfg.num_experts > 0:
                out, _ = apply_moe(pl_, h2, cfg)
                xx = xx + out
            elif cfg.d_ff > 0:
                xx = xx + apply_mlp(pl_, h2, cfg)
            return xx, (nkp, nvp)

        x, (nk, nv) = jax.lax.scan(
            body, x, (blocks, cache["k_pool"], cache["v_pool"])
        )
        logits = lm_logits(params, cfg, x)[:, 0]
        return logits, {"k_pool": nk, "v_pool": nv,
                        "page_table": page_table, "pos": pos + 1}

    if cfg.family == "ssm":
        x, new_cache = _xlstm_decode(cfg, blocks, cache, x)
    elif cfg.family == "hybrid":
        new_layers = []
        for i in range(cfg.num_layers):
            pl_ = jax.tree.map(lambda a: a[i], blocks)
            is_global = i in cfg.global_attn_layers
            x, cl = _hybrid_block_decode(cfg, pl_, cache["layers"][i], x, pos, is_global)
            new_layers.append(cl)
        new_cache = {"layers": new_layers, "pos": pos + 1}
    else:
        flags = _layer_flags(cfg)

        def body(xx, xs):
            pl_, fl, kc, vc = xs
            xx = hints.act(xx)
            h = apply_norm(pl_, "norm1", xx, cfg.norm)
            with jax.named_scope("lm.attn"):
                attn_out, nk, nv, _ = attend_decode(pl_, h, kc, vc, pos, cfg)
            xx = xx + attn_out
            h2 = apply_norm(pl_, "norm2", xx, cfg.norm)
            if cfg.num_experts > 0:
                out, _ = apply_moe(pl_, h2, cfg)
                xx = xx + out
            elif cfg.d_ff > 0:
                xx = xx + apply_mlp(pl_, h2, cfg)
            return xx, (nk, nv)

        x, (nk, nv) = jax.lax.scan(body, x, (blocks, flags, cache["k"], cache["v"]))
        new_cache = {"k": nk, "v": nv, "pos": pos + 1}

    logits = lm_logits(params, cfg, x)[:, 0]
    return logits, new_cache


def verify_step(params: Pytree, cfg: ModelConfig, cache: Pytree,
                tokens: jax.Array) -> Tuple[jax.Array, Pytree]:
    """Speculative verify: tokens (B, T) — the last committed token plus
    k = T-1 drafts — scored in ONE dispatch.  Returns
    ``(logits (B, T, V), new cache)`` where ``logits[:, i]`` is the
    target distribution for the token AFTER ``tokens[:, i]``.

    The cache comes back with all T K/V rows written and ``pos``
    advanced by T; the engine rewinds ``pos`` to ``pos + m`` after
    acceptance (rejected rows stay as dead garbage above ``pos``,
    masked out by ``kv_len`` until real tokens overwrite them).
    Dense and paged caches both verify; recurrent families cannot
    (state updates are not position-addressable, so rejected drafts
    could not be rolled back)."""
    if cfg.family in ("ssm", "hybrid") or cfg.is_encoder_decoder:
        raise ValueError(
            f"speculative verify unsupported for family {cfg.family!r}"
            f"{' (encoder-decoder)' if cfg.is_encoder_decoder else ''}: "
            f"recurrent/cross state cannot roll back rejected drafts"
        )
    pos = cache["pos"]  # (B,)
    T = tokens.shape[1]
    x = embed_tokens(params, cfg, tokens)
    blocks = params["blocks"]

    if "k_pool" in cache:
        page_table = cache["page_table"]

        def body(xx, xs):
            pl_, kp, vp = xs
            xx = hints.act(xx)
            h = apply_norm(pl_, "norm1", xx, cfg.norm)
            attn_out, nkp, nvp = attend_verify_paged(
                pl_, h, kp, vp, page_table, pos, cfg
            )
            xx = xx + attn_out
            h2 = apply_norm(pl_, "norm2", xx, cfg.norm)
            if cfg.num_experts > 0:
                out, _ = apply_moe(pl_, h2, cfg)
                xx = xx + out
            elif cfg.d_ff > 0:
                xx = xx + apply_mlp(pl_, h2, cfg)
            return xx, (nkp, nvp)

        x, (nk, nv) = jax.lax.scan(
            body, x, (blocks, cache["k_pool"], cache["v_pool"])
        )
        logits = lm_logits(params, cfg, x)  # (B, T, V)
        return logits, {"k_pool": nk, "v_pool": nv,
                        "page_table": page_table, "pos": pos + T}

    def body(xx, xs):
        pl_, kc, vc = xs
        xx = hints.act(xx)
        h = apply_norm(pl_, "norm1", xx, cfg.norm)
        attn_out, nk, nv = attend_verify(pl_, h, kc, vc, pos, cfg)
        xx = xx + attn_out
        h2 = apply_norm(pl_, "norm2", xx, cfg.norm)
        if cfg.num_experts > 0:
            out, _ = apply_moe(pl_, h2, cfg)
            xx = xx + out
        elif cfg.d_ff > 0:
            xx = xx + apply_mlp(pl_, h2, cfg)
        return xx, (nk, nv)

    x, (nk, nv) = jax.lax.scan(body, x, (blocks, cache["k"], cache["v"]))
    logits = lm_logits(params, cfg, x)  # (B, T, V)
    return logits, {"k": nk, "v": nv, "pos": pos + T}


def _xlstm_decode(cfg, blocks, cache, x):
    every = cfg.slstm_every
    pos = cache["pos"]
    if every:
        groups = cfg.num_layers // every
        m_inner = every - 1
        mparams = jax.tree.map(
            lambda a: a.reshape((groups, m_inner) + a.shape[1:]), blocks["mlstm"]
        )
        new_m, new_s = [], []
        for g in range(groups):
            m_g = []
            for j in range(m_inner):
                pl_ = jax.tree.map(lambda a: a[g][j], mparams)
                st = jax.tree.map(lambda a: a[g][j], cache["mlstm"])
                x, st = rec.decode_mlstm(pl_, st, x, cfg)
                m_g.append(st)
            new_m.append(jax.tree.map(lambda *a: jnp.stack(a), *m_g))
            sp = jax.tree.map(lambda a: a[g], blocks["slstm"])
            st = jax.tree.map(lambda a: a[g], cache["slstm"])
            x, st = rec.decode_slstm(sp, st, x, cfg)
            new_s.append(st)
        m = jax.tree.map(lambda *a: jnp.stack(a), *new_m)
        s = jax.tree.map(lambda *a: jnp.stack(a), *new_s)
        return x, {"mlstm": m, "slstm": s, "pos": pos + 1}
    new_m = []
    for l in range(cfg.num_layers):
        pl_ = jax.tree.map(lambda a: a[l], blocks["mlstm"])
        st = jax.tree.map(lambda a: a[l], cache["mlstm"])
        x, st = rec.decode_mlstm(pl_, st, x, cfg)
        new_m.append(st)
    return x, {"mlstm": jax.tree.map(lambda *a: jnp.stack(a), *new_m), "pos": pos + 1}


def _hybrid_block_decode(cfg, p, cl, x, pos, is_global: bool):
    W = 0 if is_global else cfg.sliding_window
    h = apply_norm(p, "norm1", x, cfg.norm)
    if is_global:
        attn_out, nk, nv, _ = attend_decode(p, h, cl["k"], cl["v"], pos, cfg)
        nsp = cl["slot_pos"]
    else:
        attn_out, nk, nv, nsp = attend_decode(
            p, h, cl["k"], cl["v"], pos, cfg,
            window=cfg.sliding_window, slot_pos=cl["slot_pos"],
        )
    ssm_out, nssm = rec.decode_ssm(p, cl["ssm"], h, cfg)
    mix = 0.5 * (attn_out * p["fuse_attn"].astype(x.dtype)
                 + ssm_out * p["fuse_ssm"].astype(x.dtype))
    x = x + mix
    h2 = apply_norm(p, "norm2", x, cfg.norm)
    x = x + apply_mlp(p, h2, cfg)
    return x, {"k": nk, "v": nv, "slot_pos": nsp, "ssm": nssm}
