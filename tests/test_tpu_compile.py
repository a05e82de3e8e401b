"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is attached: each test lowers and compiles one kernel call,
through its ``kernels.ops`` wrapper with the ``tpu`` backend, at a
published model's widths for the first device of a ``v5e:2x2``
topology.  The TPU compiler then refuses what the chip would refuse
(block tiling, VMEM), which interpret mode never does.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.  Keep these tests in this
one file for that reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_backend():
    """The ``tpu`` kernel backend, and no persistent compilation cache:
    a compile for a described chip is written to it but can never be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    prev = ops.get_backend()
    ops.set_backend("tpu")
    yield
    ops.set_backend(prev)
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the kernel, not an XLA fallback
    return text


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# qwen2-1.5b attention: 12 query heads, 2 KV heads, head_dim 128
H, KH, D = 12, 2, 128


@pytest.mark.parametrize("S", [8, 16, 32, 512])
def test_flash_attention_qwen2(one_chip, tpu_backend, S):
    """Prefill at the engine's smallest buckets and a long prompt."""
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
             one_chip, ((4, S, H, D), BF16), ((4, S, KH, D), BF16),
             ((4, S, KH, D), BF16))


def test_flash_attention_grad_qwen2(one_chip, tpu_backend):
    """The train step's attention: Pallas forward (with its log-sum-exp
    output) under the custom VJP, XLA flash backward."""
    def loss(q, k, v):
        return ops.flash_attention(q, k, v, causal=True).astype(F32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
             ((4, 1024, H, D), BF16), ((4, 1024, KH, D), BF16),
             ((4, 1024, KH, D), BF16))


@pytest.mark.parametrize("page", [8, 16])
def test_paged_decode_attention_qwen2(one_chip, tpu_backend, page):
    B, max_pages = 4, 8
    P = 1 + B * max_pages
    _compile(lambda q, kp, vp, t, n: ops.paged_decode_attention(
                 q, kp, vp, t, kv_len=n),
             one_chip, ((B, 1, H, D), BF16), ((KH, P, page, D), BF16),
             ((KH, P, page, D), BF16), ((B, max_pages), I32), ((B,), I32))


@pytest.mark.parametrize("heads,max_pages", [
    pytest.param(12, 128, id="qwen2-chat"),   # max_seq 2048
    pytest.param(32, 520, id="glm4-docs"),    # max_seq 8320
])
def test_paged_decode_attention_cells(one_chip, tpu_backend, heads,
                                      max_pages):
    """The serving cells' decode shapes: 16 slots of 16-token pages,
    qwen2-1.5b (G=6, 128 table slots) and glm4-9b (32 heads over 2 KV
    heads, G=16, 520 slots, which the 16-page blocks do not divide)."""
    B, page = 16, 16
    P = 1 + B * max_pages
    _compile(lambda q, kp, vp, t, n: ops.paged_decode_attention(
                 q, kp, vp, t, kv_len=n),
             one_chip, ((B, 1, heads, D), BF16), ((KH, P, page, D), BF16),
             ((KH, P, page, D), BF16), ((B, max_pages), I32), ((B,), I32))


def test_paged_verify_attention_qwen2(one_chip, tpu_backend):
    """Speculative verify at spec_k=4: 5 positions x 6 heads per KV
    head = 30 query rows per page walk."""
    B, max_pages, page = 4, 8, 16
    P = 1 + B * max_pages
    _compile(lambda q, kp, vp, t, n: ops.paged_decode_attention_mq(
                 q, kp, vp, t, base_len=n),
             one_chip, ((B, 5, H, D), BF16), ((KH, P, page, D), BF16),
             ((KH, P, page, D), BF16), ((B, max_pages), I32), ((B,), I32))


@pytest.mark.parametrize("S", [8, 512])
def test_mlstm_scan_xlstm_125m(one_chip, tpu_backend, S):
    """xlstm-125m mLSTM: 4 heads of 384 (d_model 768, expand 2)."""
    B, NH, DH = 2, 4, 384
    _compile(ops.mlstm_scan, one_chip,
             ((B, NH, S, DH), BF16), ((B, NH, S, DH), BF16),
             ((B, NH, S, DH), BF16), ((B, NH, S), F32), ((B, NH, S), F32))


def test_ssm_scan_hymba(one_chip, tpu_backend):
    """hymba-1.5b selective scan: d_inner 3200 (padded to the 256-wide
    channel block), state 16."""
    B, S, Din, N = 2, 256, 3200, 16
    _compile(ops.ssm_scan, one_chip,
             ((B, S, Din), BF16), ((B, S, Din), BF16), ((Din, N), F32),
             ((B, S, N), BF16), ((B, S, N), BF16), ((Din,), F32))


def test_moe_gmm_phi35_moe(one_chip, tpu_backend):
    """phi3.5-moe expert matmul: d_model 4096 -> d_ff 6400, 16 experts,
    tiled over F and D so each weight block fits VMEM."""
    M, Dm, F, E = 512, 4096, 6400, 16
    _compile(ops.moe_gmm, one_chip, ((M, Dm), BF16), ((E,), I32),
             ((E, Dm, F), BF16))
