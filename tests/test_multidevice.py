"""Multi-device semantics via subprocesses (the main process is locked to one
CPU device; these spawn fresh interpreters with
--xla_force_host_platform_device_count).

Covers: sharded train step == single-device train step (SPMD correctness),
pipeline-parallel stage loop, elastic checkpoint resharding.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

# the subprocesses import jax with a rebuilt PYTHONPATH, so gate on the
# parent's view of the install (optional dep: skip whole module when absent)
pytest.importorskip("jax")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=ROOT)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS
        from repro.configs import get_config
        from repro.models import lm_init, param_values, is_param
        from repro.parallel.sharding import mesh_context, logical_sharding
        from repro.launch.mesh import make_mesh, rules_for
        from repro.train import AdamWConfig, adamw_init
        from repro.train.trainstep import make_train_step
        from repro.data import DataConfig, SyntheticLM

        cfg = get_config('tinyllama-1.1b', smoke=True)
        opt_cfg = AdamWConfig(lr=1e-3, schedule='constant', warmup_steps=0)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                      global_batch=8, seed=0))
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
        values = param_values(lm_init(jax.random.PRNGKey(0), cfg))
        opt = adamw_init(values, opt_cfg)
        step = make_train_step(cfg, opt_cfg)

        # single device
        p1, o1, m1 = jax.jit(step)(values, opt, batch)

        # 4x2 (data, model) mesh
        mesh = make_mesh((4, 2), ('data', 'model'))
        rules = rules_for(cfg, 'train')
        with mesh, mesh_context(mesh, rules):
            ptree = jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg))
            psh = jax.tree.map(lambda p: logical_sharding(p.axes, mesh),
                               ptree, is_leaf=is_param)
            vs = jax.device_put(values, psh)
            os_ = adamw_init(vs, opt_cfg)
            p2, o2, m2 = jax.jit(step)(vs, os_, batch)
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
        worst = max(jax.tree_util.tree_leaves(d))
        print('LOSS', float(m1['loss']), float(m2['loss']), 'WORST', worst)
        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-3
        assert worst < 5e-3, worst
        print('OK')
    """)
    out = run_py(code, devices=8)
    assert "OK" in out


def test_pipeline_stage_loop_matches_sequential():
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import pipeline_apply

        P, M, mb, d = 4, 8, 2, 16
        mesh = jax.make_mesh((P,), ('pod',))
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (P, d, d)) / np.sqrt(d)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))

        def fn(w, h):
            return jnp.tanh(h @ w)

        got = pipeline_apply(fn, ws, x, mesh, axis='pod')
        want = x
        for s in range(P):
            want = jnp.tanh(want @ ws[s])
        err = float(jnp.max(jnp.abs(got - want)))
        print('ERR', err)
        assert err < 1e-5, err
        print('OK')
    """)
    out = run_py(code, devices=4)
    assert "OK" in out


def test_dryrun_cli_multi_pod_cell(tmp_path):
    """The dry-run entrypoint end-to-end: one light cell on the 512-device
    multi-pod mesh must lower, compile, and emit its roofline JSON."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)  # dryrun.py sets its own 512-device flag
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm-350m",
         "--shape", "decode_32k", "--mesh", "multi", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    path = os.path.join(str(tmp_path),
                        "xlstm-350m__decode_32k__pod2x16x16.json")
    assert os.path.exists(path)
    with open(path) as f:
        row = json.load(f)
    assert row["devices"] == 512
    assert row["bottleneck"] in ("compute", "memory", "collective")


def test_tripaware_collective_counting():
    """Collectives inside a scan body count trip-count times (the basis of
    the roofline collective term)."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as PS
        from repro.launch.mesh import make_mesh
        from repro.launch.roofline import (collective_bytes,
                                           collective_bytes_tripaware)
        mesh = make_mesh((2, 4), ('data', 'model'))
        w1 = jax.device_put(jnp.ones((16, 64, 64)),
                            NamedSharding(mesh, PS(None, None, 'model')))
        def f(x, w1):
            def body(c, w):
                y = c @ w
                y = jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, PS('data', None)))
                return jnp.tanh(y), None
            y, _ = jax.lax.scan(body, x, w1)
            return y.sum()
        x = jax.device_put(jnp.ones((8, 64)),
                           NamedSharding(mesh, PS('data', None)))
        text = jax.jit(jax.grad(f)).lower(x, w1).compile().as_text()
        plain, _ = collective_bytes(text)
        aware, _ = collective_bytes_tripaware(text)
        assert plain > 0
        ratio = aware / plain
        print('RATIO', ratio)
        assert 8 <= ratio <= 16.5, ratio   # 16-step scan dominates
        print('OK')
    """)
    out = run_py(code, devices=8)
    assert "OK" in out


def test_elastic_restart_reshards_checkpoint(tmp_path):
    save_code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as PS
        from repro.checkpoint import CheckpointConfig, CheckpointManager
        mesh = jax.make_mesh((8,), ('model',))
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh, PS('model', None)))
        mgr = CheckpointManager(CheckpointConfig(directory=r'{tmp_path}',
                                                 async_save=False))
        mgr.save(5, {{'w': w}})
        print('SAVED')
    """)
    out = run_py(save_code, devices=8)
    assert "SAVED" in out
    restore_code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as PS
        from repro.checkpoint import CheckpointManager, CheckpointConfig, reshard_to
        from repro.runtime import plan_mesh, build_mesh
        # restart on 6 devices: elastic plan keeps model axis = 2
        plan = plan_mesh(6, model_parallel=2)
        mesh = build_mesh(plan)
        mgr = CheckpointManager(CheckpointConfig(directory=r'{tmp_path}'))
        restored, meta = mgr.restore({{'w': np.zeros((8, 8), np.float32)}})
        sh = {{'w': NamedSharding(mesh, PS('model', None))}}
        w = reshard_to(restored, sh)['w']
        assert meta['step'] == 5
        np.testing.assert_array_equal(np.asarray(w),
                                      np.arange(64.0).reshape(8, 8))
        print('RESHARDED to', w.sharding)
    """)
    out = run_py(restore_code, devices=6)
    assert "RESHARDED" in out
