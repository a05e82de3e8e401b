"""``chip_smoke.py`` on the CPU: its phases on reduced configs, its
refusal to run without a TPU, and the compile-cache location rule the
entry points share."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


def test_serve_phase_reduced(tmp_path):
    out = chip_smoke.serve_phase(str(tmp_path), scale="reduced",
                                 smoke_batch=2)
    for engine in ("fused", "paged"):
        assert out[engine]["requests"] == 4
        assert out[engine]["tokens"] > 0


def test_logits_phase_reduced_paged_kernel():
    """The paged Pallas kernel (interpreted) against the dense path."""
    ops.set_backend("interpret")
    try:
        out = chip_smoke.logits_phase(scale="reduced")
    finally:
        ops.set_backend("ref")
    assert out["max_abs_diff"] <= chip_smoke.BF16_RTOL * out["max_abs_logit"]


def test_paged_from_dense_is_sensitive_to_the_page_table():
    """Mapping the slots' pages to each other must change the logits:
    the comparison reads the pool through the table."""
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.models import build_model

    model = build_model(reduced(get_config(chip_smoke.ARCH)))
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1, 256)
    _, cache = model.prefill(params, tokens, max_seq=32)
    nxt = jnp.ones((2, 1), jnp.int32)
    dense, _ = model.decode_step(params, cache, nxt)
    paged = chip_smoke._paged_from_dense(cache, 16)
    same, _ = model.decode_step(params, paged, nxt)
    swapped, _ = model.decode_step(
        params, {**paged, "page_table": paged["page_table"][::-1]}, nxt)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(dense))
    assert np.abs(np.asarray(swapped) - np.asarray(dense)).max() > 1e-3


@pytest.fixture
def _keep_cache_dir():
    """The launchers point JAX's compile cache at the checkout; keep the
    rest of this test process off it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_train_phase_reduced(tmp_path, monkeypatch, _keep_cache_dir):
    # a set variable keeps the launcher from pointing this process's
    # compile cache at the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    out = chip_smoke.train_phase(str(tmp_path), full=False, layers=2,
                                 batch=2, seq=32, steps=2)
    assert len(out["losses"]) == 2 and np.isfinite(out["losses"]).all()


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_compile_cache_defaults_to_checkout(monkeypatch, _keep_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_dir_is_used_alone(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiles land there and the
    helper sets no directory of its own."""
    env_dir = tmp_path / "cache"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch import compile_cache\n"
        "print(compile_cache.enable())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x @ x + 1)(jnp.ones((64, 64))).block_until_ready()\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=str(tmp_path),
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(env_dir),
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
             "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert lines == [str(env_dir), str(env_dir)]
    assert os.listdir(env_dir)
