"""Transport→trainer coupling layer (engine-derived drop schedules,
CollectiveMode dispatch, the sharded coded lossy_psum roundtrip)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.transport import (BatchedEngine, NetworkParams, SimParams,
                                  coupling)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_PARAMS = SimParams(net=NetworkParams(n_nodes=32,
                                           burst_on_prob=0.0008))


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


# ------------------------------------------------------------- schedules

def test_schedule_matches_engine_round_stats():
    """The coupling layer must not distort engine output: schedule rate
    at step i == 1 - recv_frac of engine round i, same window math."""
    eng = BatchedEngine(SMOKE_PARAMS)
    tr = eng.traces(["roce", "celeris"], 40, seed=3, legacy_streams=False)
    base = eng.assemble(tr["roce"], 3)
    to = float(np.percentile(base.times_us, 50) + base.times_us.std()) * 0.8
    stats = eng.assemble(tr["celeris"], 3, celeris_timeout_us=to,
                         adaptive=False, window="round")
    sched = coupling.schedule_from_engine(40, seed=3, params=SMOKE_PARAMS,
                                          timeout_scale=0.8)
    np.testing.assert_allclose(
        sched.rates, np.clip(1.0 - stats.recv_frac, 0, coupling.MAX_DROP),
        atol=1e-12)
    assert sched.mean > 0.0          # the tight window actually drops data


def test_adaptive_schedule_uses_timeout_controller():
    """adaptive=True must reproduce the engine's controller-windowed
    recv_frac — i.e. the schedule really is the timeout controller's
    doing, not the fixed window's."""
    fixed = coupling.schedule_from_engine(60, seed=1, params=SMOKE_PARAMS,
                                          timeout_scale=0.8)
    adap = coupling.schedule_from_engine(60, seed=1, params=SMOKE_PARAMS,
                                         timeout_scale=0.8, adaptive=True)
    eng = BatchedEngine(SMOKE_PARAMS)
    tr = eng.traces(["roce", "celeris"], 60, seed=1, legacy_streams=False)
    base = eng.assemble(tr["roce"], 1)
    to = float(np.percentile(base.times_us, 50) + base.times_us.std()) * 0.8
    ref = eng.assemble(tr["celeris"], 1, celeris_timeout_us=to,
                       adaptive=True, window="round")
    np.testing.assert_allclose(adap.rates,
                               np.clip(1.0 - ref.recv_frac, 0,
                                       coupling.MAX_DROP), atol=1e-12)
    assert not np.allclose(adap.rates, fixed.rates)


def test_closed_form_matches_standalone_straggler_model():
    """LatencyTail is the trainer's StragglerModel with bursts off —
    identical drop for identical timeouts."""
    from repro.train.trainer import StragglerModel
    sm = StragglerModel(median_latency=1.3, sigma=0.45, burst_prob=0.0)
    tail = coupling.LatencyTail(median_latency=1.3, sigma=0.45)
    rng = np.random.default_rng(0)
    timeouts = np.linspace(0.2, 6.0, 23)
    want = np.array([sm.drop_rate(t, rng) for t in timeouts])
    got = coupling.closed_form_schedule(timeouts, tail).rates
    np.testing.assert_allclose(got, want, atol=1e-12)


def test_lossless_designs_give_zero_schedule():
    for d in ("roce", "irn"):
        s = coupling.schedule_from_engine(10, seed=0, params=SMOKE_PARAMS,
                                          design=d)
        assert s.mean == 0.0


def test_drop_schedule_clip_wrap_and_straggler_walk():
    s = coupling.DropSchedule(rates=np.array([0.1, 0.9, -0.2]), source="t")
    assert s.rates.max() <= coupling.MAX_DROP and s.rates.min() >= 0.0
    assert s.rate(0) == s.rate(3) == pytest.approx(0.1)    # wraps
    m = coupling.EngineStragglerModel(s)
    seen = [m.drop_rate(2.0, None) for _ in range(4)]
    assert seen[:3] == [s.rate(i) for i in range(3)]
    assert seen[3] == s.rate(0)
    assert m.steps_taken == 4


def test_collective_mode_parse():
    CM = coupling.CollectiveMode
    assert CM.parse("lossy+hadamard") is CM.LOSSY_HADAMARD
    assert CM.parse("LOSSY-HADAMARD") is CM.LOSSY_HADAMARD
    assert CM.parse(CM.EXACT) is CM.EXACT
    assert not CM.EXACT.lossy and CM.LOSSY.lossy
    assert CM.LOSSY_HADAMARD.coded and not CM.LOSSY.coded
    with pytest.raises(ValueError):
        CM.parse("bogus")


def test_celeris_config_mode_resolution():
    from repro.train.train_step import CelerisConfig
    CM = coupling.CollectiveMode
    assert CelerisConfig().collective_mode() is CM.EXACT
    assert CelerisConfig(mode="lossy").collective_mode() is CM.LOSSY
    assert (CelerisConfig(mode="lossy+hadamard").collective_mode()
            is CM.LOSSY_HADAMARD)
    assert (CelerisConfig(mode=CM.HIERARCHICAL).collective_mode()
            is CM.HIERARCHICAL)


# ------------------------------------- sharded roundtrip (8-device mesh)

def test_sharded_lossy_psum_roundtrip_engine_rate():
    """The coded lossy_psum on an 8-device mesh, drop rate taken
    from an engine schedule, vs the single-device exact sum: zero-drop
    agrees to the coding tolerance (2e-3, see tests/test_coding.py);
    at the engine's realized rate the unbiased estimate stays within
    the documented 50% relative-error envelope and the realized
    received fraction tracks 1 - drop."""
    sched = coupling.schedule_from_engine(20, seed=0, params=SMOKE_PARAMS,
                                          timeout_scale=0.8)
    drop = float(np.clip(sched.mean, 0.02, 0.2))
    _run(f"""
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
                                        drop_rate=p)
            return est[None], (jnp.sum(counts) / (8 * plan.n_rot))[None]
        sm = jax.jit(lambda x, key, p: shd.shard_map(
            f, mesh=mesh, in_specs=(P('data', None), P(), P(), P('data')),
            out_specs=(P('data', None), P('data')), check_vma=False,
        )(x, key, p, jnp.arange(8)))
        exact = np.asarray(xs.sum(0))
        est0, _ = sm(xs, jax.random.PRNGKey(1), jnp.float32(0.0))
        np.testing.assert_allclose(np.asarray(est0[0]), exact,
                                   rtol=2e-3, atol=2e-3)
        est, frac = sm(xs, jax.random.PRNGKey(2), jnp.float32({drop}))
        assert abs(float(frac[0]) - (1 - {drop})) < 0.05, float(frac[0])
        rel = (np.linalg.norm(np.asarray(est[0]) - exact)
               / np.linalg.norm(exact))
        assert rel < 0.5, rel
        print('OK')
    """)


@pytest.mark.slow
def test_scale_check_512_lowers_plain_collectives():
    """dryrun --scale-check at 512 devices: the lossy+hadamard train
    step lowers with nothing but plain collectives."""
    out = _run("""
        from repro.launch import dryrun
        rec = dryrun.scale_check_cell('qwen2-0.5b', 512)
        assert rec['ok'], rec
        assert rec['illegal_collectives'] == {}, rec
        assert 'all_reduce' in rec['collective_ops'], rec
        print('OK')
    """, devices=512, timeout=560)
    assert "OK" in out


def test_plain_lossy_island_roundtrip_8dev():
    """CollectiveMode.LOSSY runs as a shard_map island
    (``_sync_grads_plain_island``) — per-(peer, wire-row) masks applied
    *before* the plain psum.  Zero drop must match the exact baseline
    (no coding in this path, so equality is tight), and at a real rate
    the realized received fraction tracks 1 - drop."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.configs as C
        from repro import sharding as shd
        from repro.data.pipeline import DataConfig, make_source
        from repro.optim.adamw import OptConfig
        from repro.train import train_step as ts, sharding_rules as rules
        mesh = shd.make_mesh((8,), ('data',))
        shd.set_global_mesh(mesh)
        cfg = C.get_smoke('qwen2-0.5b')
        src = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                     global_batch=8, seed=1))
        host = src.global_batch(0, 8)
        sp = rules.batch_specs(mesh, host)
        batch = {k: jax.device_put(
                     v, jax.sharding.NamedSharding(mesh, sp[k]))
                 for k, v in host.items()}

        def step_with(mode, drop):
            fn = ts.make_train_step(cfg, mesh, OptConfig(lr=1e-3),
                                    ts.CelerisConfig(mode=mode,
                                                     min_coded_size=1024))
            st = ts.init_state(jax.random.PRNGKey(0), cfg)
            st = jax.device_put(st, ts.state_shardings(st, mesh))
            st, m = fn(st, batch, jax.random.PRNGKey(1),
                       jnp.float32(drop))
            return {k: float(v) for k, v in m.items()}

        m_ex = step_with('exact', 0.0)
        m_l0 = step_with('lossy', 0.0)
        assert m_l0['recv_frac'] == 1.0, m_l0
        assert abs(m_l0['loss'] - m_ex['loss']) < 1e-4, (m_ex, m_l0)
        m_ld = step_with('lossy', 0.25)
        assert abs(m_ld['recv_frac'] - 0.75) < 0.05, m_ld
        assert np.isfinite(m_ld['loss'])
        print('OK')
    """)
