"""Multi-device tests: run in subprocesses with forced host device counts
so the main test process keeps its single real device."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow   # each case compiles in a fresh subprocess

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    """2x2-mesh loss/grads == single-device on the SAME padded geometry
    (padding differs by TP degree, so the unsharded reference model is
    built with the sharded geometry explicitly)."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS, reduced
        from repro.configs.model_config import ShapeConfig
        from repro.models.model import Model, build_model
        from repro.models.transformer import Geometry
        from repro.parallel.mesh import make_auto_mesh as make_mesh, use_mesh
        from repro.parallel.sharding import named_tree

        cfg = dataclasses.replace(reduced(ARCHS["smollm-135m"]),
                                  dtype="float32")
        key = jax.random.PRNGKey(0)
        shape = ShapeConfig("t", 64, 4, "train")
        mesh = make_mesh((2, 2), ("data", "model"))

        m_ref = Model(cfg=cfg, geom=Geometry.of(cfg, 2), mesh=None)
        params = m_ref.init(key)
        batch = m_ref.dummy_batch(key, shape)
        batch["labels"] = batch["tokens"]
        loss0, _ = jax.jit(m_ref.loss)(params, batch)
        g0 = jax.jit(jax.grad(lambda p: m_ref.loss(p, batch)[0]))(params)

        m_s = build_model(cfg, mesh)
        with use_mesh(mesh):
            params_s = jax.device_put(params, named_tree(mesh, m_s.specs()))
            batch_s = jax.device_put(
                batch, named_tree(mesh, m_s.batch_spec()))
            loss1, _ = jax.jit(m_s.loss)(params_s, batch_s)
            g1 = jax.jit(jax.grad(lambda p: m_s.loss(p, batch_s)[0]))(params_s)
        d_loss = abs(float(loss0) - float(loss1))
        # per-leaf relative bound: GSPMD partial-sum/scatter ordering gives
        # O(0.5%) fp32 drift on large-magnitude leaves (embed-scatter grads
        # are O(300)), and the tolerance must not depend on that magnitude
        d_grad = max(float(jnp.max(jnp.abs(a - b)))
                     / max(float(jnp.max(jnp.abs(a))), 1.0)
                     for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)))
        print("DELTA", d_loss, d_grad)
        assert d_loss < 1e-4, (float(loss0), float(loss1))
        assert d_grad < 0.01

        print("OK")
    """)
    assert "OK" in out


def test_moe_expert_parallel_matches_unsharded():
    """shard_map EP MoE == single-device MoE (same routing/capacity)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import ARCHS, reduced
        from repro.configs.model_config import ShapeConfig
        from repro.models.model import build_model
        from repro.parallel.mesh import make_auto_mesh as make_mesh, use_mesh
        from repro.parallel.sharding import named_tree

        cfg = dataclasses.replace(reduced(ARCHS["olmoe-1b-7b"]),
                                  dtype="float32")
        key = jax.random.PRNGKey(0)
        shape = ShapeConfig("t", 32, 4, "train")

        m0 = build_model(cfg, mesh=None)
        params = m0.init(key)
        batch = m0.dummy_batch(key, shape)
        batch["labels"] = batch["tokens"]
        loss0, _ = jax.jit(m0.loss)(params, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        m1 = build_model(cfg, mesh)
        with use_mesh(mesh):
            pspec = named_tree(mesh, m1.specs())
            params_s = jax.device_put(params, pspec)
            bspec = named_tree(mesh, m1.batch_spec())
            batch_s = jax.device_put(batch, bspec)
            loss1, _ = jax.jit(m1.loss)(params_s, batch_s)
        d = abs(float(loss0) - float(loss1))
        print("DELTA", d)
        # capacity is per-shard under EP so a little routing drift is
        # expected; fp32 keeps it tight
        assert d < 0.05, (float(loss0), float(loss1))
        print("OK")
    """)
    assert "OK" in out


def test_elastic_restore_onto_different_mesh(tmp_path):
    """Checkpoint saved from a 4x2 mesh restores onto 2x2 (elastic)."""
    out = _run(f"""
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS, reduced
        from repro.configs.model_config import ShapeConfig, TrainConfig
        from repro.parallel.mesh import make_auto_mesh as make_mesh
        from repro.train.trainer import Trainer

        cfg = reduced(ARCHS["smollm-135m"])
        shape = ShapeConfig("t", 64, 4, "train")
        tcfg = TrainConfig(learning_rate=1e-3)

        mesh1 = make_mesh((4, 2), ("data", "model"))
        tr1 = Trainer(cfg, shape, tcfg, mesh=mesh1,
                      ckpt_dir=r"{tmp_path}", ckpt_every=4, total_steps=4)
        tr1.run(steps=4, log_every=0)

        mesh2 = make_mesh((2, 2), ("data", "model"))
        tr2 = Trainer(cfg, shape, tcfg, mesh=mesh2,
                      ckpt_dir=r"{tmp_path}", ckpt_every=4, total_steps=8)
        log = tr2.run(steps=8, log_every=0)
        assert log[0]["step"] == 5, log[0]
        print("OK resumed-on-smaller-mesh")
    """)
    assert "OK" in out


def test_multipod_mesh_and_grad_compression():
    """pod-axis mesh builds; int8+EF compressed psum over 'pod' works."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.optim.compression import pod_allreduce_compressed
        from repro.parallel.mesh import make_auto_mesh as make_mesh, use_mesh
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        grads = {"w": jnp.arange(8, dtype=jnp.float32).reshape(2, 4) * 1e-3}
        err = {"w": jnp.zeros((2, 4))}

        def f(g, e):
            return pod_allreduce_compressed(g, e)

        sm = jax.shard_map(f, mesh=mesh,
                           in_specs=(P(), P()), out_specs=(P(), P()),
                           check_vma=False)
        with use_mesh(mesh):
            mean, new_err = jax.jit(sm)(grads, err)
        np.testing.assert_allclose(np.asarray(mean["w"]),
                                   np.asarray(grads["w"]), rtol=0.02,
                                   atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_single_cell_mini():
    """The dry-run machinery itself (reduced device count, small arch)."""
    out = _run("""
        import os
        # simulate the dryrun entry with fewer fake devices for speed
        import jax
        from repro.configs import get_arch, get_shape
        from repro.launch.dryrun import build_step
        from repro.models.model import build_model
        from repro.parallel.mesh import make_auto_mesh as make_mesh, use_mesh
        from repro.launch.hlo_cost import analyze

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_arch("smollm-135m")
        shape = get_shape("train_4k")
        model = build_model(cfg, mesh)
        with use_mesh(mesh):
            jitted, specs = build_step(model, cfg, shape, mesh)
            compiled = jitted.lower(*specs).compile()
        peak = compiled.memory_analysis().peak_memory_in_bytes
        assert peak > 0
        r = analyze(compiled.as_text())
        assert r["flops"] > 1e12
        print("OK", peak, r["flops"])
    """, devices=8)
    assert "OK" in out
