"""Distribution-layer integration: lossy collectives under shard_map,
Celeris train island on a real (host-device) mesh, dry-run lowering.

Runs in a subprocess with 8 forced host devices so the main pytest
process keeps its single-device view for the smoke tests.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


# the train step's per-leaf coded psum over 8 peers: leaf 0 of shape
# (N,), sum estimate and received fraction per shard
PSUM = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import sharding as shd
        from repro.core import coding, lossy_collectives as lc
        mesh = shd.make_mesh((8,), ('data',))
        N = 5000
        plan = coding.plan_nd((N,), None)
        signs = coding.rademacher_nd(jax.random.PRNGKey(7), plan)
        xs = jax.random.normal(jax.random.PRNGKey(0), (8, N))
        def f(x, key, p, peer):
            est, counts = lc.lossy_psum(x[0], 'data', plan=plan, signs=signs,
                                        key=key, leaf=0, peer_id=peer[0],
                                        drop_rate=p, quantize_wire=QUANTIZE)
            return est[None], (jnp.sum(counts) / (8 * plan.n_rot))[None]
        sm = jax.jit(lambda x, key, p: shd.shard_map(
            f, mesh=mesh, in_specs=(P('data', None), P(), P(), P('data')),
            out_specs=(P('data', None), P('data')), check_vma=False,
        )(x, key, p, jnp.arange(8)))
"""


def test_lossy_psum_zero_drop_equals_exact():
    out = _run(PSUM.replace("QUANTIZE", "False") + """
        est, frac = sm(xs, jax.random.PRNGKey(1), jnp.float32(0.0))
        np.testing.assert_allclose(np.asarray(est[0]), np.asarray(xs.sum(0)),
                                   rtol=2e-3, atol=2e-3)
        est5, frac5 = sm(xs, jax.random.PRNGKey(2), jnp.float32(0.05))
        assert abs(float(frac5[0]) - 0.95) < 0.04
        rel = np.linalg.norm(np.asarray(est5[0] - xs.sum(0)))
        rel /= np.linalg.norm(np.asarray(xs.sum(0)))
        assert rel < 0.5, rel
        print('OK')
    """)
    assert "OK" in out


def test_celeris_train_on_mesh_learns():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.configs as C
        from repro import sharding as shd
        from repro.data.pipeline import DataConfig, make_source
        from repro.train import train_step as ts, sharding_rules as rules
        from repro.optim.adamw import OptConfig
        mesh = shd.make_mesh((4, 2), ('data', 'model'))
        shd.set_global_mesh(mesh)
        cfg = C.get_smoke('qwen2-0.5b')
        src = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                     global_batch=8, seed=1))
        st = ts.init_state(jax.random.PRNGKey(0), cfg)
        st = jax.device_put(st, ts.state_shardings(st, mesh))
        fn = ts.make_train_step(cfg, mesh, OptConfig(lr=1e-3),
                                ts.CelerisConfig(mode="lossy_hadamard",
                                                 min_coded_size=1024))
        losses = []
        for i in range(14):
            host = src.global_batch(i, 4)
            sp = rules.batch_specs(mesh, host)
            b = {k: jax.device_put(v, jax.sharding.NamedSharding(mesh, sp[k]))
                 for k, v in host.items()}
            st, m = fn(st, b, jax.random.fold_in(jax.random.PRNGKey(3), i),
                       jnp.float32(0.05))
            losses.append(float(m['loss']))
        assert np.isfinite(losses).all()
        # robust to step-level noise from the lossy sync: trend must be down
        assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses
        assert 0.9 < float(m['recv_frac']) < 1.0
        print('OK')
    """)
    assert "OK" in out


def test_moe_ep_on_mesh_matches_single_device():
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        import repro.configs as C
        from repro import sharding as shd
        from repro.models import moe as MOE
        cfg = C.get_smoke('qwen2-moe-a2.7b')
        # generous capacity: no token dropping -> paths must agree exactly
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=50.0))
        p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model),
                              jnp.float32) * 0.3
        shd.set_global_mesh(None)
        y_local, aux_local, _ = MOE.moe_block(p, cfg, x)
        mesh = shd.make_mesh((4, 2), ('data', 'model'))
        shd.set_global_mesh(mesh)
        y_ep, aux_ep, _ = jax.jit(lambda p_, x_: MOE.moe_block(p_, cfg, x_))(p, x)
        shd.set_global_mesh(None)
        np.testing.assert_allclose(np.asarray(y_ep, np.float32),
                                   np.asarray(y_local, np.float32),
                                   rtol=1e-4, atol=1e-5)
        print('OK')
    """)
    assert "OK" in out


def test_router_bias_on_expert_parallel_mesh():
    """DeepSeek-V3's router with its bias on the expert-parallel path
    (all experts, a model axis): the same per-layer counts as the
    dropless path, and a train step on the mesh moves every bias entry
    by bias_rate."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        import repro.configs as C
        from repro import sharding as shd
        from repro.models import moe as MOE
        from repro.optim.adamw import OptConfig
        from repro.train import train_step as ts, sharding_rules as rules
        cfg = dataclasses.replace(C.get_smoke('moonlight-16b-a3b'),
                                  dtype='float32')
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=50.0))
        p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
        p['bias'] = jax.random.normal(jax.random.PRNGKey(2), (16,)) * 0.05
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
        shd.set_global_mesh(None)
        _, _, local = MOE.moe_block(p, cfg, x)
        mesh = shd.make_mesh((4, 2), ('data', 'model'))
        shd.set_global_mesh(mesh)
        _, _, ep = jax.jit(lambda p_, x_: MOE.moe_block(p_, cfg, x_))(p, x)
        np.testing.assert_array_equal(np.asarray(ep['moe_counts']),
                                      np.asarray(local['moe_counts']))
        st = ts.init_state(jax.random.PRNGKey(0), cfg)
        st = jax.device_put(st, ts.state_shardings(st, mesh))
        fn = ts.make_train_step(cfg, mesh, OptConfig(lr=1e-3),
                                ts.CelerisConfig(mode='lossy_hadamard',
                                                 min_coded_size=1024))
        tok = jax.random.randint(jax.random.PRNGKey(3), (8, 64), 0, 512)
        host = {'tokens': tok, 'labels': tok}
        sp = rules.batch_specs(mesh, host)
        b = {k: jax.device_put(v, jax.sharding.NamedSharding(mesh, sp[k]))
             for k, v in host.items()}
        st, m = fn(st, b, jax.random.PRNGKey(4), jnp.float32(0.0))
        assert np.isfinite(float(m['loss']))
        bias = np.asarray(st['params']['decoder']['groups'][0]['moe']['bias'])
        np.testing.assert_allclose(np.abs(bias), 0.001)
        print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_cell_compiles_and_fits():
    """One full production-mesh dry-run cell end-to-end (512 devices)."""
    out = _run("""
        from repro.launch import dryrun
        rec = dryrun.lower_cell('qwen2-0.5b', 'train_4k', multi_pod=False)
        assert rec['memory']['peak_bytes'] < 16 * 2**30, rec['memory']
        assert rec['roofline']['useful_flops_ratio'] > 0.3
        assert rec['collective_bytes_total'] > 0
        print('OK')
    """, devices=512, timeout=560)
    assert "OK" in out


def test_elastic_restart_across_meshes(tmp_path):
    """Checkpoint saved under one topology restores under another."""
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        import repro.configs as C
        from repro import sharding as shd
        from repro.checkpoint import checkpoint as ckpt
        from repro.train import train_step as ts
        cfg = C.get_smoke('qwen2-0.5b')
        st = ts.init_state(jax.random.PRNGKey(0), cfg)
        mesh1 = shd.make_mesh((4, 2), ('data', 'model'))
        st1 = jax.device_put(st, ts.state_shardings(st, mesh1))
        ckpt.save({str(tmp_path)!r}, 3, st1)
        # restore onto a different mesh shape
        mesh2 = shd.make_mesh((2, 4), ('data', 'model'))
        st2, step, _ = ckpt.restore({str(tmp_path)!r}, st,
                                    shardings=ts.state_shardings(st, mesh2))
        assert step == 3
        for a, b in zip(jax.tree.leaves(st1), jax.tree.leaves(st2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('OK')
    """)
    assert "OK" in out


def test_lossy_psum_quantized_wire_close_to_f32():
    """quantize_wire=True (the trainer's wire: a pmax-shared scale per
    wire row, stochastic floor, int16 psum) stays an unbiased-ish
    estimate: the zero-drop reduce matches the exact sum to
    quantization tolerance."""
    out = _run(PSUM.replace("QUANTIZE", "True") + """
        est, frac = sm(xs, jax.random.PRNGKey(1), jnp.float32(0.0))
        assert float(frac[0]) == 1.0
        want = np.asarray(xs.sum(0))
        err = np.linalg.norm(np.asarray(est[0]) - want) / np.linalg.norm(want)
        assert err < 0.05, err
        print('OK')
    """)
    assert "OK" in out
