"""Dry-run machinery validated in-process on a small forced-device mesh
(subprocess so the 512-device env of the real dry-run never leaks into
the test session) + HLO analyzer unit tests."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": f"{REPO}/src"}


def _run_py(code: str, timeout=560):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=ENV, cwd=REPO,
                          timeout=timeout)


@pytest.mark.slow
def test_small_mesh_cell_lowers_and_compiles():
    """A miniature of the production dry-run: 8 fake devices, 4x2 mesh,
    one train cell + one decode cell lower AND compile; collectives appear."""
    r = _run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, jax
        from repro.launch.cells import build_cell, analyze_compiled
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        out = {}
        for arch, shape in [("qwen2-1.5b", "train_4k"), ("glm4-9b", "decode_32k")]:
            cell = build_cell(arch, shape, mesh)
            with mesh:
                comp = cell.fn.lower(*cell.args).compile()
            st = analyze_compiled(comp)
            out[f"{arch}|{shape}"] = {
                "flops": st.get("flops", 0),
                "coll_ops": st["collectives"]["total_ops"],
                "temp": st.get("temp_size_in_bytes", 0),
                "hlo_flops": st.get("hlo_stats", {}).get("flops", 0),
            }
        print(json.dumps(out))
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    for key, st in out.items():
        assert st["flops"] > 0, key
        assert st["coll_ops"] > 0, key  # SPMD inserted collectives
        assert st["hlo_flops"] > 0, key


def test_make_production_mesh_shapes():
    r = _run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        print(m1.devices.shape, m1.axis_names)
        print(m2.devices.shape, m2.axis_names)
    """)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert "(16, 16) ('data', 'model')" in lines[0]
    assert "(2, 16, 16) ('pod', 'data', 'model')" in lines[1]


# ---------------------------------------------------------------------------
# HLO analyzer unit tests (fast, in-process)
# ---------------------------------------------------------------------------
def test_hlo_analyzer_counts_scan_flops_exactly():
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_stats import analyze_hlo

    L, B, D = 5, 8, 64

    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    w = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    comp = jax.jit(jax.grad(f, argnums=(0, 1))).lower(x, w).compile()
    st = analyze_hlo(comp.as_text())
    expect = 3 * L * 2 * B * D * D  # fwd + 2 bwd dots per layer
    assert abs(st["flops"] - expect) / expect < 1e-6


def test_hlo_analyzer_nested_loops():
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_stats import analyze_hlo

    B, D, L1, L2 = 4, 32, 3, 7

    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return jnp.tanh(ci @ w), None
            ci, _ = jax.lax.scan(inner, c, None, length=L2)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=L1)
        return y

    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    w = jax.ShapeDtypeStruct((D, D), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    st = analyze_hlo(comp.as_text())
    expect = L1 * L2 * 2 * B * D * D
    assert abs(st["flops"] - expect) / expect < 1e-6


def test_collective_parser_on_synthetic_hlo():
    from repro.launch.cells import parse_collectives

    text = """
ENTRY %main (p: f32[8,8]) -> f32[8,8] {
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[4096]{0} all-gather(%y), replica_groups=[2,4]<=[8], dimensions={0}
  %rs = f32[256]{0} reduce-scatter(%z), replica_groups={{0,1},{2,3}}, to_apply=%add
}
"""
    out = parse_collectives(text)
    assert out["operand_bytes_by_kind"]["all-reduce"] == 4096
    assert out["operand_bytes_by_kind"]["all-gather"] == 4096 * 4 / 4
    assert out["operand_bytes_by_kind"]["reduce-scatter"] == 1024 * 2
    assert out["total_ops"] == 3


@pytest.mark.slow
def test_elastic_rescale_across_mesh_sizes(tmp_path):
    """Elastic restart drill: checkpoint written under a 4-device mesh is
    restored and resharded onto an 8-device mesh (different dp degree);
    gathered parameter values must be identical."""
    ckpt_dir = str(tmp_path / "ckpt")
    r1 = _run_py(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.checkpoint import Checkpointer
        from repro.configs import get_config, reduced
        from repro.models import build_model
        from repro.parallel import Plan
        from repro.parallel.sharding import make_param_shardings
        from repro.launch.mesh import make_mesh
        from repro.train import OptimizerConfig, init_train_state

        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = reduced(get_config("qwen2-1.5b"))
        model = build_model(cfg)
        plan = Plan()
        state = init_train_state(model, jax.random.PRNGKey(0), OptimizerConfig(), plan)
        specs, axes = model.param_specs()
        shardings = make_param_shardings(mesh, axes, specs, plan)
        state["params"] = jax.device_put(state["params"], shardings)
        ck = Checkpointer({ckpt_dir!r}, keep=1)
        ck.save(5, state, blocking=True)
        print("SAVED", float(jax.tree.leaves(state["params"])[0].sum()))
    """)
    assert r1.returncode == 0, r1.stderr[-2000:]
    saved_sum = float(r1.stdout.strip().splitlines()[-1].split()[-1])

    r2 = _run_py(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.checkpoint import Checkpointer
        from repro.configs import get_config, reduced
        from repro.ft.elastic import elastic_restart
        from repro.models import build_model
        from repro.parallel import Plan
        from repro.launch.mesh import make_mesh
        from repro.train import OptimizerConfig, init_train_state

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = reduced(get_config("qwen2-1.5b"))
        model = build_model(cfg)
        plan = Plan()
        like = init_train_state(model, jax.random.PRNGKey(1), OptimizerConfig(), plan)
        ck = Checkpointer({ckpt_dir!r}, keep=1)
        state, step = elastic_restart(ck, like, model, mesh, plan)
        assert step == 5, step
        leaf = jax.tree.leaves(state["params"])[0]
        assert len(leaf.sharding.device_set) > 1  # actually resharded
        print("RESTORED", float(leaf.sum()))
    """)
    assert r2.returncode == 0, r2.stderr[-2000:]
    restored_sum = float(r2.stdout.strip().splitlines()[-1].split()[-1])
    assert abs(saved_sum - restored_sum) < 1e-3
