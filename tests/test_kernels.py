"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes, plus the custom-VJP XLA flash attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_xla import flash_attention_xla


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.fixture(autouse=True)
def _interpret_backend():
    ops.set_backend("interpret")
    yield
    ops.set_backend("ref")


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ===========================================================================
# flash attention (Pallas)
# ===========================================================================
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,T,H,KH,D,causal,window",
    [
        (1, 64, 64, 4, 4, 32, True, 0),     # MHA causal
        (2, 64, 64, 4, 2, 32, True, 0),     # GQA
        (2, 96, 96, 4, 1, 16, True, 0),     # MQA, ragged seq
        (1, 64, 64, 2, 2, 48, False, 0),    # bidirectional, padded head_dim
        (2, 128, 128, 4, 2, 32, True, 32),  # sliding window
        (1, 32, 128, 2, 2, 32, False, 0),   # cross-attention T != S
    ],
)
def test_flash_attention_matches_oracle(rng, B, S, T, H, KH, D, causal, window, dtype):
    q = _rand(rng, (B, S, H, D), dtype)
    k = _rand(rng, (B, T, KH, D), dtype)
    v = _rand(rng, (B, T, KH, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_k=32)
    want = ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


def test_flash_attention_q_offset(rng):
    """Continuation chunk: q at positions 32..63 against kv 0..63."""
    B, H, D = 1, 2, 32
    q = _rand(rng, (B, 32, H, D), jnp.float32)
    k = _rand(rng, (B, 64, H, D), jnp.float32)
    v = _rand(rng, (B, 64, H, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, q_offset=32,
                              block_q=16, block_k=16)
    want = ref.attention(q, k, v, causal=True, q_offset=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal,window,q_offset", [
    (True, 0, 0), (False, 0, 0), (True, 32, 0), (True, 0, 16)])
def test_flash_attention_grads_match_oracle(rng, causal, window, q_offset):
    """Pallas forward + XLA flash backward (the custom VJP of the
    tpu/interpret backends) against autodiff of the oracle."""
    B, S, H, KH, D = 2, 64, 4, 2, 32
    q = _rand(rng, (B, S, H, D), jnp.float32)
    k = _rand(rng, (B, S + q_offset, KH, D), jnp.float32)
    v = _rand(rng, (B, S + q_offset, KH, D), jnp.float32)
    w = _rand(rng, (B, S, H, D), jnp.float32)  # a non-uniform cotangent

    def f(q, k, v):
        out = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, block_q=16, block_k=16)
        return (out * w).sum()

    def g(q, k, v):
        out = ref.attention(q, k, v, causal=causal, window=window,
                            q_offset=q_offset)
        return (out * w).sum()

    np.testing.assert_allclose(f(q, k, v), g(q, k, v), rtol=1e-5)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("op", ["mlstm_scan", "ssm_scan", "moe_gmm"])
def test_forward_only_kernels_refuse_grad(rng, op):
    """Kernels without a backward raise under jax.grad instead of
    differentiating an oracle in their place."""
    x = _rand(rng, (1, 2, 8, 8), jnp.float32)
    calls = {
        "mlstm_scan": lambda x: ops.mlstm_scan(x, x, x, x[..., 0], x[..., 0]),
        "ssm_scan": lambda x: ops.ssm_scan(
            x[0], x[0], -jnp.ones((8, 4)), x[0, :, :, :4], x[0, :, :, :4],
            jnp.ones((8,))),
        "moe_gmm": lambda x: ops.moe_gmm(
            x[0, 0], jnp.array([8], jnp.int32), x[0, :1], block_m=8),
    }
    fn = calls[op]
    assert np.isfinite(np.asarray(fn(x))).all()  # the forward runs
    with pytest.raises(NotImplementedError, match=f"{op}.*no backward"):
        jax.grad(lambda x: fn(x).sum())(x)


def test_backend_resolves_from_device():
    """Unset, the backend follows the device: ref on the CPU."""
    saved = ops._BACKEND
    try:
        ops._BACKEND = None
        assert ops.get_backend() == ("tpu" if jax.default_backend() == "tpu"
                                     else "ref")
    finally:
        ops._BACKEND = saved
    with pytest.raises(ValueError):
        ops.set_backend("cuda")


# ===========================================================================
# XLA flash attention (custom VJP) — fwd and grads vs oracle
# ===========================================================================
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
def test_flash_xla_grads(rng, causal, window):
    B, S, H, KH, D = 2, 200, 4, 2, 16
    q = _rand(rng, (B, S, H, D), jnp.float32)
    k = _rand(rng, (B, S, KH, D), jnp.float32)
    v = _rand(rng, (B, S, KH, D), jnp.float32)

    def f(q, k, v):
        return flash_attention_xla(q, k, v, causal, window, 0, 64, 64).sum()

    def g(q, k, v):
        return ref.attention(q, k, v, causal=causal, window=window).sum()

    np.testing.assert_allclose(f(q, k, v), g(q, k, v), rtol=1e-5)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# ===========================================================================
# mLSTM chunked scan
# ===========================================================================
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,S,D,chunk", [
    (1, 2, 32, 16, 8),
    (2, 2, 48, 16, 16),
    (1, 4, 64, 32, 32),
    (2, 1, 40, 8, 16),  # ragged: S % chunk != 0
])
def test_mlstm_matches_oracle(rng, B, H, S, D, chunk, dtype):
    q = _rand(rng, (B, H, S, D), dtype)
    k = _rand(rng, (B, H, S, D), dtype)
    v = _rand(rng, (B, H, S, D), dtype)
    ip = _rand(rng, (B, H, S), jnp.float32)
    fp = _rand(rng, (B, H, S), jnp.float32) + 1.0
    out = ops.mlstm_scan(q, k, v, ip, fp, chunk=chunk)
    want, _ = ref.mlstm_scan(q, k, v, ip, fp)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=5 * TOL[dtype], rtol=5 * TOL[dtype],
    )


def test_mlstm_step_continues_scan(rng):
    """Decode step from scan-final state == one longer scan."""
    B, H, S, D = 1, 2, 16, 8
    q = _rand(rng, (B, H, S + 1, D), jnp.float32)
    k = _rand(rng, (B, H, S + 1, D), jnp.float32)
    v = _rand(rng, (B, H, S + 1, D), jnp.float32)
    ip = _rand(rng, (B, H, S + 1), jnp.float32)
    fp = _rand(rng, (B, H, S + 1), jnp.float32)
    full, _ = ref.mlstm_scan(q, k, v, ip, fp)
    _, state = ref.mlstm_scan(q[:, :, :S], k[:, :, :S], v[:, :, :S],
                              ip[:, :, :S], fp[:, :, :S])
    h, _ = ops.mlstm_step(q[:, :, S], k[:, :, S], v[:, :, S],
                          ip[:, :, S], fp[:, :, S], state)
    np.testing.assert_allclose(np.asarray(h), np.asarray(full[:, :, S]),
                               atol=1e-5)


# ===========================================================================
# selective scan (mamba)
# ===========================================================================
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Din,N,bd,chunk", [
    (1, 16, 16, 8, 8, 8),
    (2, 32, 24, 8, 8, 16),
    (1, 40, 32, 16, 16, 8),  # ragged seq
])
def test_ssm_matches_oracle(rng, B, S, Din, N, bd, chunk, dtype):
    x = _rand(rng, (B, S, Din), dtype)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, Din))) * 0.1 + 0.01, dtype)
    A = jnp.asarray(-np.abs(rng.normal(size=(Din, N))) - 0.1, jnp.float32)
    Bm = _rand(rng, (B, S, N), dtype)
    Cm = _rand(rng, (B, S, N), dtype)
    D = _rand(rng, (Din,), jnp.float32)
    out = ops.ssm_scan(x, dt, A, Bm, Cm, D, block_d=bd, chunk=chunk)
    want, _ = ref.ssm_scan(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=5 * TOL[dtype], rtol=5 * TOL[dtype],
    )


def test_ssm_step_continues_scan(rng):
    B, S, Din, N = 1, 12, 8, 4
    x = _rand(rng, (B, S + 1, Din), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S + 1, Din))) * 0.1 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(Din, N))) - 0.1, jnp.float32)
    Bm = _rand(rng, (B, S + 1, N), jnp.float32)
    Cm = _rand(rng, (B, S + 1, N), jnp.float32)
    D = _rand(rng, (Din,), jnp.float32)
    full, _ = ref.ssm_scan(x, dt, A, Bm, Cm, D)
    _, h = ref.ssm_scan(x[:, :S], dt[:, :S], A, Bm[:, :S], Cm[:, :S], D)
    y, _ = ops.ssm_step(x[:, S], dt[:, S], A, Bm[:, S], Cm[:, S], D, h)
    np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, S]), atol=1e-5)


# ===========================================================================
# MoE grouped matmul
# ===========================================================================
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,D,F,E,bm", [
    (32, 8, 16, 4, 8),
    (64, 16, 24, 4, 16),
    (48, 8, 8, 8, 16),   # ragged M
])
def test_moe_gmm_matches_oracle(rng, M, D, F, E, bm, dtype):
    toks = _rand(rng, (M, D), dtype)
    sizes = rng.multinomial(M, np.ones(E) / E).astype(np.int32)
    w = _rand(rng, (E, D, F), dtype)
    out = ops.moe_gmm(toks, jnp.asarray(sizes), w, block_m=bm)
    want = ref.moe_gmm(toks, jnp.asarray(sizes), w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=5 * TOL[dtype], rtol=5 * TOL[dtype],
    )


def test_moe_gmm_tiled_matches_oracle(rng):
    """Wide enough that F (640 -> five 128-wide blocks) and D (1024 ->
    two 512-wide blocks) are tiled: accumulation over the expert and
    contraction grid axes."""
    M, D, F, E = 64, 1024, 640, 4
    toks = _rand(rng, (M, D), jnp.float32)
    sizes = jnp.asarray(rng.multinomial(M, np.ones(E) / E).astype(np.int32))
    w = _rand(rng, (E, D, F), jnp.float32)
    out = ops.moe_gmm(toks, sizes, w, block_m=16)
    want = ref.moe_gmm(toks, sizes, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-3, rtol=1e-4)


def test_moe_gmm_empty_groups(rng):
    toks = _rand(rng, (16, 8), jnp.float32)
    sizes = jnp.array([0, 16, 0, 0], jnp.int32)
    w = _rand(rng, (4, 8, 8), jnp.float32)
    out = ops.moe_gmm(toks, sizes, w, block_m=8)
    want = toks @ w[1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


# ===========================================================================
# Triangular flash attention (causal block skip + fused backward)
# ===========================================================================
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
def test_flash_tri_matches_oracle(rng, causal, window):
    from repro.kernels.flash_tri import flash_attention_tri

    B, S, H, KH, D = 2, 300, 4, 2, 16
    q = _rand(rng, (B, S, H, D), jnp.float32)
    k = _rand(rng, (B, S, KH, D), jnp.float32)
    v = _rand(rng, (B, S, KH, D), jnp.float32)

    def f(q, k, v):
        return flash_attention_tri(q, k, v, causal, window, 0, 64, 64).sum()

    def g(q, k, v):
        return ref.attention(q, k, v, causal=causal, window=window).sum()

    np.testing.assert_allclose(f(q, k, v), g(q, k, v), rtol=1e-5)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_flash_tri_skips_causal_blocks():
    """The triangular pair list must be ~half the full square."""
    from repro.kernels.flash_tri import _pairs

    qi, ki, last = _pairs(8, 8, 64, 64, True, 0, 0, "q")
    assert len(qi) == 8 * 9 // 2  # lower triangle incl. diagonal
    qi2, _, _ = _pairs(8, 8, 64, 64, False, 0, 0, "q")
    assert len(qi2) == 64
    # sliding window restricts to a band
    qi3, _, _ = _pairs(8, 8, 64, 64, True, 128, 0, "q")
    assert len(qi3) < len(qi)


def test_ssm_ckpt_vjp_matches_autodiff(rng):
    """Checkpointed-adjoint chunked scan: fwd + all six grads vs the
    autodiff-through-scan oracle."""
    import jax
    from repro.kernels.ssm_vjp import ssm_scan_ckpt

    B, S, Din, N = 2, 37, 12, 8
    x = _rand(rng, (B, S, Din), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, Din))) * 0.1 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(Din, N))) - 0.1, jnp.float32)
    Bm = _rand(rng, (B, S, N), jnp.float32)
    Cm = _rand(rng, (B, S, N), jnp.float32)
    D = _rand(rng, (Din,), jnp.float32)

    w = jnp.arange(Din, dtype=jnp.float32)
    f = lambda *a: (ssm_scan_ckpt(*a, 8) * w).sum()
    g = lambda *a: (ref.ssm_scan(*a)[0] * w).sum()
    np.testing.assert_allclose(f(x, dt, A, Bm, Cm, D), g(x, dt, A, Bm, Cm, D),
                               rtol=1e-5)
    gf = jax.grad(f, argnums=tuple(range(6)))(x, dt, A, Bm, Cm, D)
    gr = jax.grad(g, argnums=tuple(range(6)))(x, dt, A, Bm, Cm, D)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_ssm_chunked_matches_oracle(rng):
    B, S, Din, N = 2, 37, 24, 8
    x = _rand(rng, (B, S, Din), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, Din))) * 0.1 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(Din, N))) - 0.1, jnp.float32)
    Bm = _rand(rng, (B, S, N), jnp.float32)
    Cm = _rand(rng, (B, S, N), jnp.float32)
    D = _rand(rng, (Din,), jnp.float32)
    y1, _ = ref.ssm_scan(x, dt, A, Bm, Cm, D)
    y2, _ = ref.ssm_scan_chunked(x, dt, A, Bm, Cm, D, chunk=8)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
