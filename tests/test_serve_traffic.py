"""Serving traffic + coded-KV transfer (ISSUE 7 serve-path tests).

- request process: seeded determinism, open-loop design independence,
  load scaling of the arrival rate;
- queue simulation: block conservation (shipped == delivered demand of
  completed requests plus partial progress), latency monotone in
  round times, censoring accounted;
- KV hole masks: seeded, mean tracks the delivered fraction;
- degraded decode: full-mask roundtrip is exact for both wire layouts,
  and at a lossy fraction the Hadamard layout's usable-context
  fraction beats the uncoded contiguous layout (the fig8 recovery
  claim in miniature).
"""
import numpy as np
import pytest

from repro.core.transport import coupling
from repro.serve import traffic

TP = traffic.ServeTrafficParams(n_prefill=12, n_decode=3,
                                steps_per_round=4)


def _trace(load=0.7, seed=0, horizon=3e5, ref=1e4):
    tp = traffic.ServeTrafficParams(
        n_prefill=12, n_decode=3, steps_per_round=4, load=load)
    return tp, traffic.request_trace(tp, horizon, ref, seed)


def test_request_trace_deterministic_and_open_loop():
    tp, tr1 = _trace(seed=3)
    _, tr2 = _trace(seed=3)
    np.testing.assert_array_equal(tr1.arrival_us, tr2.arrival_us)
    np.testing.assert_array_equal(tr1.kv_blocks, tr2.kv_blocks)
    _, tr3 = _trace(seed=4)
    assert not np.array_equal(tr1.arrival_us, tr3.arrival_us)
    # arrivals are sorted, inside the horizon, lengths positive
    assert (np.diff(tr1.arrival_us) >= 0).all()
    assert tr1.arrival_us[-1] < 3e5
    assert (tr1.kv_blocks >= 1).all() and (tr1.decode_tokens >= 1).all()
    assert (tr1.ready_us >= tr1.arrival_us).all()


def test_arrival_rate_scales_with_load():
    tp_lo, tr_lo = _trace(load=0.4, seed=1)
    tp_hi, tr_hi = _trace(load=0.8, seed=1)
    r = tr_hi.n_requests / max(tr_lo.n_requests, 1)
    assert 1.6 < r < 2.4          # ~2x requests at 2x load
    assert (traffic.arrival_rate_per_us(tp_hi, 1e4)
            == pytest.approx(2 * traffic.arrival_rate_per_us(tp_lo, 1e4)))


def test_simulate_serving_conservation_and_censoring():
    tp, tr = _trace(load=0.7, seed=5)
    times = np.full(30, 1e4)
    recv = np.ones(30)
    sim = traffic.simulate_serving(tp, times, recv, tr)
    # conservation: total shipped == full demand of completed requests
    # + partial progress of the censored ones (recv_frac == 1 here)
    got_blocks = np.round(sim.kv_frac * tr.kv_blocks).astype(int)
    assert sim.blocks_shipped == got_blocks.sum()
    assert (got_blocks[sim.completed] == tr.kv_blocks[sim.completed]).all()
    assert sim.blocks_shipped <= 30 * tp.capacity_blocks_per_round
    # completed requests: latency positive; censored: horizon remainder
    assert (sim.latency_us[sim.completed] > 0).all()
    horizon = times.sum()
    cens = ~sim.completed
    np.testing.assert_allclose(
        sim.latency_us[cens],
        np.maximum(horizon - tr.arrival_us[cens], 0.0))


def test_serving_latency_monotone_in_round_time():
    """Same trace over 2x slower rounds -> worse p99 (the backlog is
    the figure's design discriminator)."""
    tp, tr = _trace(load=0.8, seed=2)
    fast = traffic.simulate_serving(tp, np.full(30, 1e4), np.ones(30), tr)
    slow = traffic.simulate_serving(tp, np.full(30, 2e4), np.ones(30), tr)
    assert slow.p99_latency_us > fast.p99_latency_us


def test_recv_frac_flows_into_kv_frac():
    tp, tr = _trace(load=0.5, seed=6)
    cut = np.full(30, 0.9)
    sim = traffic.simulate_serving(tp, np.full(30, 1e4), cut, tr)
    done = sim.completed
    assert done.any()
    np.testing.assert_allclose(sim.kv_frac[done], 0.9, rtol=1e-12)
    assert sim.mean_kv_frac == pytest.approx(0.9)


def test_kv_hole_masks_seeded_and_calibrated():
    f = np.array([0.25, 0.6, 0.95, 1.0])
    m1 = coupling.kv_hole_masks(f, 4096, seed=9)
    m2 = coupling.kv_hole_masks(f, 4096, seed=9)
    np.testing.assert_array_equal(m1, m2)
    assert m1.shape == (4, 4096) and m1.dtype == bool
    np.testing.assert_allclose(m1.mean(axis=1), f, atol=0.03)
    assert m1[3].all()                      # frac 1.0 -> no holes
    m3 = coupling.kv_hole_masks(f, 4096, seed=10)
    assert not np.array_equal(m1, m3)


# ----------------------------------------------- degraded-KV decode

def test_kv_wire_roundtrip_exact_and_coded_beats_uncoded():
    """Full mask -> bitwise-faithful roundtrip both ways; lossy mask ->
    the coded layout keeps more usable context than contiguous chunks
    (fig8's recovery metric, one payload in miniature)."""
    import jax
    import jax.numpy as jnp
    from repro.core import coding
    from repro.serve import serve_step

    n_rot = 64
    x = jax.random.normal(jax.random.PRNGKey(0), (n_rot * 37,))
    plan = coding.plan_nd((int(x.size),), None, n_rot)
    signs = coding.rademacher_nd(jax.random.PRNGKey(1), plan)

    full = jnp.ones(n_rot)
    for coded in (True, False):
        y = serve_step.kv_wire_roundtrip(x, full, signs, plan, coded=coded)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   atol=1e-5)

    mask = jnp.asarray(
        coupling.kv_hole_masks(np.array([0.85]), n_rot, seed=0)[0])
    lost = n_rot - int(mask.sum())
    assert 0 < lost < n_rot
    # "positions" = contiguous spans, one per uncoded wire chunk; the
    # usable-context metric is per-position relative L2 (fig8's TAU)
    usable = {}
    for coded in (True, False):
        y = serve_step.kv_wire_roundtrip(x, mask, signs, plan, coded=coded)
        d = np.asarray(y - x).reshape(n_rot, -1)
        r = np.asarray(x).reshape(n_rot, -1)
        rel = np.linalg.norm(d, axis=1) / np.linalg.norm(r, axis=1)
        usable[coded] = float((rel <= 0.6).mean())
    # uncoded: each lost chunk annihilates exactly one position span
    assert usable[False] == pytest.approx(1.0 - lost / n_rot)
    # coded: the same loss lands as dense small noise across all spans
    assert usable[True] > usable[False]
    assert usable[True] >= 0.9


@pytest.mark.slow
def test_degrade_caches_full_mask_is_identity():
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import model as M
    from repro.serve import serve_step

    cfg = C.get_smoke("qwen2-0.5b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                                cfg.vocab_size)
    prefill = serve_step.make_prefill(cfg, 24)
    _, caches = prefill(params, {"tokens": prompt})
    full = jnp.ones(64)
    same = serve_step.degrade_caches(caches, full, jax.random.PRNGKey(2))
    err = serve_step.kv_position_error(caches, same, 16)
    assert float(err.max()) < 1e-2          # bf16 roundtrip noise only
