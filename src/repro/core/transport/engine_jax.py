"""Accelerator-native transport engine: a jitted ``lax.scan`` backend.

The numpy :class:`~repro.core.transport.engine.BatchedEngine` is the
bit-pinning source of truth — every seeded statistic in tests/data is
defined by its exact draw order and float op sequence.  This module is
the throughput backend: the same physics as
``BatchedEngine._traces_shared``, restructured so the rate-dependent
hot loop runs as pure ``jax.numpy`` ops under ``jit`` with the seed
axis vmapped.

Hybrid split (the replay contract decides what goes where)
----------------------------------------------------------
Everything that *consumes a random substream* stays host-side numpy,
block for block in the numpy engine's exact order — the burst chains,
the hot-row ECN/drop curves that gate CNP and loss draws, the PFC
cascade, and the per-design loss draws (via the shared helpers in
:mod:`designs`).  Loss draws depend only on the drop curve, never on
the DCQCN rate, so each design's recovery machinery reduces to two
dense rate-independent fields::

    excess_time = A + B * pkt_time        (reliable designs)
    delivered   = n_pkts - wire_losses    (celeris)

Everything *rate-dependent* runs jitted and vmapped over seeds: the
DCQCN recurrence as one ``lax.scan`` over steps (CNP steps apply
:func:`dcqcn.step_math`, calm gaps advance closed-form via
:func:`dcqcn.calm_ramp` inside the scan body — the same dual f32/f64
emission as ``rate_trace``), the queue/bandwidth response curves
(shared formula source: :mod:`network`), per-design completion times,
fault availability overlays, and the per-step reductions.  The fixed
round/phase window assembly has a jitted twin used by
``BatchedEngine.assemble`` under ``backend="jax"``.

Tolerance contract
------------------
The host pass replays the numpy engine's streams bit-exactly, so the
two backends see identical draws; the jitted arithmetic regroups a few
float accumulations (the A/B split above, XLA ``pow``/sum orderings),
leaving relative differences at the 1e-7 level on step traces.  The
A/B harness (``tests/test_engine_jax.py``) pins agreement on p99,
delivered fractions, per-tier loss and per-pod recombination to
``rtol=1e-5``.  Anything tighter than that is not part of the
contract — bit-level questions are always settled by the numpy
backend.
"""
from __future__ import annotations

import numpy as np

from repro.core.transport import dcqcn, designs, faults, network, topology
from repro.core.transport import engine as engine_mod
from repro.core.transport.params import SimParams

import jax
import jax.numpy as jnp
from jax import lax

# Trace-time counter: incremented once per jit compilation of a core
# (the function body only runs while tracing).  The jit-cache-reuse
# test asserts a second identical call leaves it untouched.
TRACE_COUNT = [0]

# Compiled cores / window kernels per static configuration.  jit itself
# caches per input shape on top (one full block + at most one partial
# tail block per trace length).
_CORE_CACHE: dict = {}
_WINDOW_CACHE: dict = {}


# ----------------------------------------------------------------------
# DCQCN recurrence as a scan (mirror of dcqcn.rate_trace)
# ----------------------------------------------------------------------

def _dcqcn_scan(cnp, cc, dq):
    """(tb, n) bool CNP block -> (tb, n) f32 rates + final f64 state.

    The carry holds the last *materialized* state (the anchor) plus the
    calm-gap length ``L`` since it.  Calm steps only bump ``L``; the
    emitted rate is the f32 closed-form ramp from the anchor — exactly
    ``rate_trace``'s gap fill.  A CNP step advances the anchor in f64
    closed form, applies :func:`dcqcn.step_math`, emits the advanced
    (pre-step) rate, and resets ``L`` — exactly the sequential
    ``use rate; step()`` order.  The block end materializes the
    trailing gap, matching ``rate_trace``'s final ``_advance_calm``.
    """
    decay = np.float64(1.0 - dq.alpha_g)

    def body(carry, cnp_t):
        r, t, a, g, L = carry
        any_t = cnp_t.any()
        # dual emission, as in rate_trace: calm steps fill the trace
        # from the f32-cast anchor; CNP steps emit the f64-advanced
        # state cast to f32
        calm32 = dcqcn.calm_ramp(r.astype(jnp.float32),
                                 t.astype(jnp.float32), g, L, dq,
                                 dtype=np.float32, xp=jnp)
        r64 = dcqcn.calm_ramp(r, t, g, L, dq, dtype=np.float64, xp=jnp)
        emit = jnp.where(any_t, r64.astype(jnp.float32), calm32)
        a_adv = a * jnp.power(decay, L.astype(jnp.float64))
        g_adv = g + L
        r_s, t_s, a_s, g_s = dcqcn.step_math(r64, t, a_adv, g_adv,
                                             cnp_t, dq, xp=jnp)
        new = (jnp.where(any_t, r_s, r), jnp.where(any_t, t_s, t),
               jnp.where(any_t, a_s, a), jnp.where(any_t, g_s, g),
               jnp.where(any_t, jnp.int32(0), L + 1))
        return new, emit

    carry0 = (cc["rate"], cc["target"], cc["alpha"], cc["good"],
              jnp.int32(0))
    (r, t, a, g, L), rates = lax.scan(body, carry0, cnp)
    cc_out = {
        "rate": dcqcn.calm_ramp(r, t, g, L, dq, dtype=np.float64, xp=jnp),
        "target": t,
        "alpha": a * jnp.power(decay, L.astype(jnp.float64)),
        "good": g + L}
    return rates, cc_out


# ----------------------------------------------------------------------
# The jitted per-block core (vmapped over the seed axis)
# ----------------------------------------------------------------------

def _phase_statics(p: SimParams, plan, hgs, ph_pkts, ph_fan, ph_inc):
    """Static per-phase column vectors the rate assembly multiplies by.

    The DCI oversubscription and incast fan divisors are data-independent
    per column, so they fold into ``(n_flows,)`` constants applied to
    every step of the phase — multiplying/dividing the untouched
    columns by exactly 1.0 keeps them bit-identical to the numpy
    engine's sliced in-place mutations.
    """
    hier = p.topo.hierarchical
    out = []
    for k, ph in enumerate(plan.phases):
        f = ph.src.size
        s = dict(src=ph.src, n_pkts=ph_pkts[k],
                 tier_cols=hgs[k].tier_cols,
                 pod_cols=hgs[k].pod_cols if hier else None,
                 qd_mult=None, o_div=None, dci_add=None, fan_div=None)
        x = hgs[k].cross
        if hier and x.size:
            o32 = topology.dci_oversub_factor(p.topo, hgs[k])
            qm = np.ones(f, np.float32)
            qm[x] = o32
            od = np.ones(f, np.float32)
            od[x] = o32
            da = np.zeros(f, np.float32)
            da[x] = np.float32(p.topo.dci_rtt_us / 2.0)
            s.update(qd_mult=qm, o_div=od, dci_add=da)
        inc = ph_inc[k]
        if inc.size:
            # numpy does eff_rate[:, inc] /= fan (an f64 divide cast
            # back to f32 by the in-place ufunc); the f64 round trip
            # below reproduces that bit-for-bit, and is the exact
            # identity on the fan-1 columns
            fd = np.ones(f, np.float64)
            fd[inc] = ph_fan[k][inc]
            s["fan_div"] = fd
        out.append(s)
    return out


def _make_core(p: SimParams, plan, hgs, design_list, n, steps,
               ph_pkts, ph_steps, ph_fan, ph_inc, identity_plan):
    net, rel, dq = p.net, p.rel, p.dcqcn
    has_faults = p.fault.active
    use_rate_scale = p.fault.straggler_frac > 0
    single = plan.single_phase
    stat = _phase_statics(p, plan, hgs, ph_pkts, ph_fan, ph_inc)
    detect_for = {"roce": rel.rto_us, "irn": rel.rto_low_us,
                  "srnic": rel.rto_low_us + rel.host_slowpath_us}

    def core_one(inp):
        TRACE_COUNT[0] += 1
        cnp = inp["cnp"]
        tb = cnp.shape[0]                       # static under jit
        round0 = np.arange(0, tb, steps)
        rates, cc_out = _dcqcn_scan(cnp, inp["cc"], dq)
        out_phases = []
        for k, s in enumerate(stat):
            ph_in = inp["phases"][k]
            occ32 = ph_in["occ32"]
            if identity_plan:
                rate_ph = rates
            elif single:
                rate_ph = rates[:, s["src"]]
            else:
                rows = (round0[:, None] + ph_steps[k][None, :]).ravel()
                rate_ph = rates[rows[:, None], s["src"][None, :]]
            # response curves: the same formula source as the numpy
            # engine (network.py), evaluated on the final mutated
            # occupancies, with the DCI overlay folded into static
            # column multipliers
            qd = network.queue_delay_us(net, occ32)
            if s["qd_mult"] is not None:
                qd = qd * s["qd_mult"]
            eff = rate_ph * network.avail_bandwidth(net, occ32)
            if s["o_div"] is not None:
                eff = eff / s["o_div"]
            if s["fan_div"] is not None:
                eff = (eff.astype(jnp.float64)
                       / s["fan_div"]).astype(jnp.float32)
            if use_rate_scale:
                eff = eff * inp["rate_scale"][s["src"]]
            pkt_time = net.pkt_time_us / jnp.maximum(eff, 1e-3)
            ptf64 = pkt_time.astype(jnp.float64)
            serialize = s["n_pkts"] * pkt_time
            blocked = ph_in["blocked"] if has_faults else None
            dead = ph_in["dead"] if has_faults else None
            alive = (~dead).astype(jnp.float64) if has_faults else None
            per_design = {}
            for d in design_list:
                dd = ph_in["designs"][d]
                if d == "celeris":
                    t = (serialize + designs.CELERIS_QUEUE_OVERLAP * qd
                         + net.base_rtt_us / 2)
                    deliv = dd["deliv"]
                else:
                    t = serialize + qd + net.base_rtt_us / 2
                    if d == "roce":
                        t = t + ph_in["pfc"]
                    ex = (dd["A"].astype(jnp.float64)
                          + dd["B"].astype(jnp.float64) * ptf64)
                    t = t + ex.astype(jnp.float32)
                if s["dci_add"] is not None:
                    t = t + s["dci_add"]
                if has_faults:
                    # faults.apply_to_result, as where-ops
                    if d == "celeris":
                        deliv = jnp.where(blocked, 0.0, deliv)
                        deliv = jnp.where(dead, 0.0, deliv)
                    else:
                        t = jnp.where(blocked,
                                      2.0 * t + np.float32(detect_for[d]),
                                      t)
                        t = jnp.where(
                            dead,
                            t + np.float32(detect_for[d]
                                           * (1 + rel.max_retries)),
                            t)
                nat = t.max(axis=-1)
                if d == "celeris":
                    dsum = deliv.sum(axis=-1)
                    tier = jnp.stack([deliv[:, c].sum(axis=-1)
                                      for c in s["tier_cols"]], axis=-1)
                    pod = (jnp.stack([deliv[:, c].sum(axis=-1)
                                      for c in s["pod_cols"]], axis=-1)
                           if s["pod_cols"] is not None else None)
                elif has_faults:
                    # reliable designs deliver everything a live flow
                    # offers; only dead flows zero out
                    npk = np.float64(s["n_pkts"])
                    dsum = npk * alive.sum(axis=-1)
                    tier = jnp.stack([npk * alive[:, c].sum(axis=-1)
                                      for c in s["tier_cols"]], axis=-1)
                    pod = (jnp.stack([npk * alive[:, c].sum(axis=-1)
                                      for c in s["pod_cols"]], axis=-1)
                           if s["pod_cols"] is not None else None)
                else:
                    # constant offered=delivered sums; the host fills
                    # them without a device round trip
                    dsum = tier = pod = None
                per_design[d] = dict(nat=nat, deliv=dsum, tier=tier,
                                     pod=pod)
            out_phases.append(per_design)
        return {"cc": cc_out, "phases": out_phases}

    return jax.jit(jax.vmap(core_one))


def _core_for(p: SimParams, plan, hgs, design_list, n, steps,
              ph_pkts, ph_steps, ph_fan, ph_inc, identity_plan):
    key = (repr(p), tuple(design_list), n, steps,
           tuple((ph.src.tobytes(), ph.dst.tobytes(), int(ph.n_steps),
                  int(ph.payload_bytes)) for ph in plan.phases))
    core = _CORE_CACHE.get(key)
    if core is None:
        core = _make_core(p, plan, hgs, design_list, n, steps, ph_pkts,
                          ph_steps, ph_fan, ph_inc, identity_plan)
        _CORE_CACHE[key] = core
    return core


# ----------------------------------------------------------------------
# Host-side stream replay (the draw pass)
# ----------------------------------------------------------------------

class _SeedStreams:
    """One seed's generators + carried chain states, consumed block by
    block in ``_traces_shared``'s exact order (the replay contract)."""

    def __init__(self, eng, seed: int, design_list, hier: bool,
                 incast: bool):
        p = eng.p
        g = eng._geometry(seed)
        self.g = g
        net = p.net
        n, n_tors, steps = g["n"], g["n_tors"], g["steps"]
        self.fabric_gen = np.random.default_rng(g["fabric_seed"])
        self.cnp_gen = np.random.default_rng([seed, engine_mod._STREAM_CNP])
        self.pfc_gen = np.random.default_rng([seed, engine_mod._STREAM_PFC])
        self.transfer_gens = {
            d: np.random.default_rng(
                [seed, engine_mod._STREAM_TRANSFER[d]])
            for d in design_list}
        self.fab_state = network.FabricState(
            bursting=np.zeros(n_tors, dtype=bool),
            occupancy=np.full(n_tors, net.idle_occupancy))
        if hier:
            self.dci_state = topology.init_dci_state(net, p.topo)
            self.dci_fab_gen = np.random.default_rng(
                [g["fabric_seed"], topology.STREAM_DCI_FABRIC])
            self.dci_cnp_gen = np.random.default_rng(
                [seed, topology.STREAM_DCI_CNP])
        if incast:
            self.inc_cnp_gen = np.random.default_rng(
                [seed, engine_mod._STREAM_INCAST_CNP])
        self.fmodel = (faults.FaultModel(p, seed, n, n_tors, steps)
                       if p.fault.active else None)
        self.rate_scale = np.ones(n, dtype=np.float32)
        if self.fmodel is not None and self.fmodel.rate_scale is not None:
            self.rate_scale = self.fmodel.rate_scale


def _design_draws(d, n_pkts, drop_p, rel, net, rng, shape):
    """One design-phase's loss draws, reduced to dense rate-independent
    fields: ``A + B * pkt_time`` excess for the reliable designs,
    delivered packets for celeris.  Draw order and the drop-capable
    subset are exactly ``designs.transfer``'s (shared helpers)."""
    if d == "celeris":
        deliv = np.full(shape, n_pkts, dtype=np.float32)
        idx = np.flatnonzero(drop_p > 0)
        if idx.size:
            pf = np.ascontiguousarray(drop_p).ravel()[idx]
            deliv.flat[idx] -= designs.celeris_loss_draws(n_pkts, pf, rng)
        return {"deliv": deliv}
    A = np.zeros(shape, dtype=np.float32)
    B = np.zeros(shape, dtype=np.float32)
    if d == "roce":
        p_eff = drop_p * designs.PFC_DROP_SUPPRESSION
        idx = np.flatnonzero(p_eff > 0)
        if idx.size:
            pf = np.ascontiguousarray(p_eff).ravel()[idx]
            a = np.zeros(idx.size)
            b = np.zeros(idx.size)
            for has_loss, n_resend, detect in designs.roce_loss_episodes(
                    n_pkts, pf, rel, net, rng):
                a += np.where(has_loss, detect, 0.0)
                b += np.where(has_loss, n_resend, 0.0)
            A.flat[idx] = a
            B.flat[idx] = b
    else:  # irn / srnic
        idx = np.flatnonzero(drop_p > 0)
        if idx.size:
            pf = np.ascontiguousarray(drop_p).ravel()[idx]
            k, tail_lost, k2 = designs.sr_loss_draws(n_pkts, pf, rng)
            detect = np.where(tail_lost, rel.rto_low_us,
                              rel.nack_delay_us + net.base_rtt_us)
            a = (np.where(k > 0, detect, 0.0)
                 + np.where(k2 > 0, rel.rto_low_us, 0.0))
            if d == "srnic":
                a += k * rel.host_slowpath_us
            b = np.where(k > 0, k, 0.0) + np.where(k2 > 0, k2, 0.0)
            A.flat[idx] = a
            B.flat[idx] = b
    return {"A": A, "B": B}


def _stack_seeds(host_inputs):
    """Stack a list of per-seed input pytrees along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *host_inputs)


# ----------------------------------------------------------------------
# Entry point
# ----------------------------------------------------------------------

def traces_batched(eng, design_list, n_rounds: int, seeds, *,
                   round_block: int | None = None):
    """Physics traces for every seed in ``seeds``, one jitted pass per
    round block with the seed axis vmapped.  Returns one
    ``{design: StepTrace}`` dict per seed, interchangeable (within the
    tolerance contract) with ``BatchedEngine.traces(...,
    legacy_streams=False)`` per seed.
    """
    p = eng.p
    net, rel = p.net, p.rel
    unknown = [d for d in design_list if d not in designs.DESIGNS]
    if unknown:
        raise ValueError(f"unknown design(s) {unknown}; "
                         f"choose from {designs.DESIGNS}")
    if net.n_nodes < net.nodes_per_tor or net.n_nodes % net.nodes_per_tor:
        raise ValueError(
            f"n_nodes={net.n_nodes} must be a positive multiple of "
            f"nodes_per_tor={net.nodes_per_tor}")
    if net.ecn_threshold > net.loss_knee:
        raise ValueError(
            f"ecn_threshold={net.ecn_threshold} must not exceed "
            f"loss_knee={net.loss_knee}")
    if eng.recorder is not None:
        raise ValueError("a TraceRecorder requires backend='numpy' "
                         "(the recorder hooks ride the numpy per-phase "
                         "pass)")
    design_list = list(design_list)
    seeds = [int(s) for s in seeds]
    S = len(seeds)
    if S == 0:
        return []

    g0 = eng._geometry(seeds[0])
    n, steps, n_tors = g0["n"], g0["steps"], g0["n_tors"]
    plan = g0["plan"]
    T = n_rounds * steps
    if round_block is None:
        # the numpy default, and not negotiable: the host pass must
        # consume the PFC/transfer streams in the numpy engine's exact
        # block partition or the draws land on different cells
        round_block = max(1, engine_mod._BLOCK_ELEMENTS // (steps * n))
    block_steps = round_block * steps

    hier = p.topo.hierarchical
    dci_net = topology.dci_net_params(net, p.topo) if hier else None
    hgs = plan.geometries(net, p.topo)
    ph_pkts = [ph.n_pkts(net) for ph in plan.phases]
    ph_steps = [np.flatnonzero(plan.phase_of_step == k)
                for k in range(len(plan.phases))]
    ph_fan = [ph.fan_in() for ph in plan.phases]
    ph_inc = [np.flatnonzero(f > 1) for f in ph_fan]
    identity_plan = plan.single_phase and np.array_equal(
        plan.phases[0].src, np.arange(n))
    incast = any(inc.size for inc in ph_inc)
    has_faults = p.fault.active
    has_roce = "roce" in design_list
    single = plan.single_phase
    ph_pod_cols = [hg.pod_cols for hg in hgs] if hier else None

    core = _core_for(p, plan, hgs, tuple(design_list), n, steps,
                     ph_pkts, ph_steps, ph_fan, ph_inc, identity_plan)

    streams = [_SeedStreams(eng, s, design_list, hier, incast)
               for s in seeds]
    outs = [eng._new_traces(
        design_list, T, steps, n, (),
        tier_cols=hgs[0].tier_cols if single else None,
        tier_counts=plan.tier_counts(net, p.topo, hgs),
        tier_pkts_round=plan.tier_pkts_round(net, p.topo, hgs),
        phase_of_step=plan.phase_of_step,
        phase_budget_frac=plan.budget_fracs(),
        phase_src=tuple(ph.src for ph in plan.phases),
        phase_tier_cols=tuple(hg.tier_cols for hg in hgs),
        phase_pod_cols=tuple(ph_pod_cols) if hier else None,
        n_pods=p.topo.n_pods if hier else 0,
        pod_pkts_round=(plan.pod_pkts_round(net, p.topo, hgs)
                        if hier else None),
        step_priority=plan.step_priority()) for _ in seeds]
    fault_flows = ([np.zeros(T) for _ in seeds] if has_faults else None)

    def host_block(st: _SeedStreams, t0: int, tb: int, si: int):
        """One seed's draw pass for steps [t0, t0+tb): exactly
        ``_traces_shared``'s stream consumption, minus the
        rate-dependent math the core does."""
        u = st.fabric_gen.random((tb, network._ADVANCE_DRAWS, n_tors))
        _, occ_tor, st.fab_state = network.occupancy_trace(
            net, u, st.fab_state)
        occ_dci = None
        if hier:
            u_dci = st.dci_fab_gen.random(
                (tb, network._ADVANCE_DRAWS, p.topo.n_pods))
            _, occ_dci, st.dci_state = network.occupancy_trace(
                dci_net, u_dci, st.dci_state)
        cnp = np.zeros((tb, n), dtype=bool)
        round0 = np.arange(0, tb, steps)
        ph_host = []
        # phase pass 1: curves + CNP draws (numpy engine order)
        for k, ph in enumerate(plan.phases):
            rows = (round0[:, None] + ph_steps[k][None, :]).ravel()
            occ_ph = occ_tor[rows] if not single else occ_tor
            ecn_p, drop_p, hot = engine_mod._sparse_path_curves(
                net, occ_ph, ph.src, ph.dst)
            occ32 = network.path_occupancy_trace(
                net, occ_ph.astype(np.float32), ph.src, ph.dst)
            occ_eff = None
            if hier:
                occ_eff = topology.overlay_curves(
                    net, p.topo, hgs[k], occ_ph,
                    occ_dci[rows] if not single else occ_dci,
                    ecn_p, drop_p)
            cnp_ph = np.zeros((rows.size, ph.src.size), dtype=bool)
            cnp_ph[hot] = (st.cnp_gen.random((hot.size, ph.src.size))
                           < ecn_p[hot])
            if hier:
                topology.dci_cnp_draws(hgs[k], ecn_p, cnp_ph,
                                       st.dci_cnp_gen)
            inc = ph_inc[k]
            if inc.size:
                occ_inc = np.maximum(occ32[:, inc],
                                     (1.0 - 1.0 / ph_fan[k][inc]
                                      ).astype(occ32.dtype))
                occ32[:, inc] = occ_inc
                ecn_inc = network.ecn_mark_prob(net, occ_inc)
                drop_p[:, inc] = network.drop_prob(net, occ_inc)
                cnp_ph[:, inc] = (st.inc_cnp_gen.random(occ_inc.shape)
                                  < ecn_inc)
            cnp[np.ix_(rows, ph.src)] = cnp_ph
            ph_host.append([rows, occ32, drop_p, occ_eff])

        blk = st.fmodel.advance(t0, tb) if st.fmodel is not None else None

        # phase pass 2: final occupancy mutation + fault masks
        for k, ph in enumerate(plan.phases):
            rows, occ32, drop_p, occ_eff = ph_host[k]
            if hier and hgs[k].cross.size:
                occ32[:, hgs[k].cross] = occ_eff.astype(np.float32)
            blocked = dead = None
            if st.fmodel is not None:
                blocked, dead = st.fmodel.phase_masks(
                    blk, rows, ph, hgs[k], net.nodes_per_tor)
                nf = ((blocked.sum(axis=1) if blocked is not None else 0)
                      + (dead.sum(axis=1) if dead is not None else 0))
                fault_flows[si][t0 + rows] = nf
            ph_host[k] = [rows, occ32, drop_p, blocked, dead, None, {}]

        # design loop: PFC + loss draws (numpy engine order — the PFC
        # stream is consumed only on the roce iterations, per phase)
        for d in design_list:
            for k in range(len(plan.phases)):
                rows, occ32, drop_p, blocked, dead, pfc, dd = ph_host[k]
                if d == "roce":
                    pfc = network.pfc_pause_trace(net, occ32, st.pfc_gen)
                    ph_host[k][5] = pfc
                dd[d] = _design_draws(d, ph_pkts[k], drop_p, rel, net,
                                      st.transfer_gens[d], occ32.shape)

        phases_in = []
        for k in range(len(plan.phases)):
            rows, occ32, drop_p, blocked, dead, pfc, dd = ph_host[k]
            ph_in = {"occ32": occ32, "designs": dd}
            if has_roce:
                ph_in["pfc"] = pfc
            if has_faults:
                shape = occ32.shape
                ph_in["blocked"] = (blocked if blocked is not None
                                    else np.zeros(shape, dtype=bool))
                ph_in["dead"] = (dead if dead is not None
                                 else np.zeros(shape, dtype=bool))
            phases_in.append(ph_in)
        return {"cnp": cnp, "phases": phases_in}

    cc = {"rate": np.ones((S, n)), "target": np.ones((S, n)),
          "alpha": np.ones((S, n)),
          "good": np.zeros((S, n), dtype=np.int32)}
    rate_scales = np.stack([st.rate_scale for st in streams])

    with jax.enable_x64(True):
        for t0 in range(0, T, block_steps):
            tb = min(block_steps, T - t0)
            host = [host_block(st, t0, tb, si)
                    for si, st in enumerate(streams)]
            inp = _stack_seeds(host)
            inp["cc"] = cc
            inp["rate_scale"] = rate_scales
            res = jax.device_get(core(inp))
            cc = res["cc"]
            for si in range(S):
                _scatter_block(outs[si], res, si, t0, plan, ph_steps,
                               ph_pkts, hgs, ph_pod_cols, tb, steps,
                               has_faults)

    if has_faults:
        for si in range(S):
            for tr in outs[si].values():
                tr.fault_flows = fault_flows[si]
    return outs


def _scatter_block(out, res, si, t0, plan, ph_steps, ph_pkts, hgs,
                   ph_pod_cols, tb, steps, has_faults):
    """Write one seed's block of core outputs into its StepTraces; the
    offered totals are schedule constants filled host-side."""
    round0 = np.arange(0, tb, steps)
    for k, ph in enumerate(plan.phases):
        rows = t0 + (round0[:, None] + ph_steps[k][None, :]).ravel()
        f = ph.src.size
        n_pkts = ph_pkts[k]
        for d, tr in out.items():
            o = res["phases"][k][d]
            tr.nat_us[rows] = o["nat"][si]
            tr.total[rows] = float(n_pkts * f)
            if o["deliv"] is not None:
                tr.deliv[rows] = o["deliv"][si]
            else:
                tr.deliv[rows] = float(n_pkts * f)
            if tr.tier_deliv is not None:
                for kt, cols in enumerate(hgs[k].tier_cols):
                    tr.tier_total[rows, kt] = float(n_pkts * cols.size)
                    if o["tier"] is not None:
                        tr.tier_deliv[rows, kt] = o["tier"][si][:, kt]
                    else:
                        tr.tier_deliv[rows, kt] = float(n_pkts * cols.size)
            if tr.pod_deliv is not None and ph_pod_cols is not None:
                for kp, cols in enumerate(ph_pod_cols[k]):
                    tr.pod_total[rows, kp] = float(n_pkts * cols.size)
                    if o["pod"] is not None:
                        tr.pod_deliv[rows, kp] = o["pod"][si][:, kp]
                    else:
                        tr.pod_deliv[rows, kp] = float(n_pkts * cols.size)


# ----------------------------------------------------------------------
# Jitted fixed bounded-window assembly
# ----------------------------------------------------------------------

def _cumsum1(x):
    """Inclusive prefix sum along axis 1.  ``jnp.cumsum`` on f64 takes
    XLA:TPU minutes to compile (its f64 is emulated, and the windowed
    cumsum lowering grows with the axis); the associative scan compiles
    in under a second and differs only in f64 summation order."""
    return lax.associative_scan(jnp.add, x, axis=1)


def _make_window(ph_rows, ph_frac, n_groups, perms=None):
    """Jitted twin of ``BatchedEngine._assemble_phase_window_fixed``
    (which the round window is the single-phase case of).

    ``perms`` (``cut_order="priority"``; one static permutation per
    phase block) mirrors ``engine._priority_survive``: each over-budget
    block's cut is reallocated across steps in the static priority
    order, leaving times and total delivered packets untouched."""
    invs = ([np.argsort(p) for p in perms] if perms is not None else None)

    def fn(nat, deliv, budget_us, group_delivs):
        R = nat.shape[0]
        times = jnp.zeros(R)
        got = jnp.zeros(R)
        got_g = [jnp.zeros((R, g.shape[2])) for g in group_delivs]
        for k, rows in enumerate(ph_rows):
            b_k = budget_us * ph_frac[k]
            nat_k = nat[:, rows]
            cum = _cumsum1(nat_k)
            total_t = cum[:, -1]
            over = total_t > b_k
            times = times + jnp.where(over, b_k, total_t)
            done = cum <= b_k
            bidx = jnp.argmax(~done, axis=1)
            prev = jnp.where(
                bidx > 0,
                jnp.take_along_axis(cum, jnp.maximum(bidx - 1, 0)[:, None],
                                    axis=1)[:, 0],
                0.0)
            d_k = deliv[:, rows]
            part = (b_k - prev) / jnp.maximum(
                jnp.take_along_axis(nat_k, bidx[:, None], axis=1)[:, 0],
                1e-9)
            got_k = ((d_k * done).sum(axis=1)
                     + jnp.take_along_axis(d_k, bidx[:, None],
                                           axis=1)[:, 0] * part)
            got = got + jnp.where(over, got_k, d_k.sum(axis=1))
            survive = None
            if perms is not None:
                K = jnp.where(over, d_k.sum(axis=1) - got_k, 0.0)
                d_perm = d_k[:, perms[k]]
                cum_d = _cumsum1(d_perm)
                cutfrac = jnp.clip(
                    (K[:, None] - (cum_d - d_perm))
                    / jnp.maximum(d_perm, 1e-30), 0.0, 1.0)
                survive = (1.0 - cutfrac)[:, invs[k]]
            for i in range(n_groups):
                gd_k = group_delivs[i][:, rows]
                if survive is not None:
                    cut = (gd_k * survive[:, :, None]).sum(axis=1)
                else:
                    cut = ((gd_k * done[:, :, None]).sum(axis=1)
                           + gd_k[jnp.arange(R), bidx] * part[:, None])
                got_g[i] = got_g[i] + jnp.where(over[:, None], cut,
                                                gd_k.sum(axis=1))
        return times, got, got_g

    return jax.jit(fn)


def assemble_window_fixed(nat, deliv, tot_sum, budget_us, groups,
                          ph_rows, ph_frac, perms=None):
    """Fixed round/phase bounded window on (R, steps) arrays, jitted.

    Same signature contract as the numpy fixed-window helpers: returns
    ``(times, fracs, group_fracs)``.  Pass a single phase covering the
    round for the round window; ``perms`` selects the priority cut
    order (one static permutation per phase block, None = arrival).
    """
    ph_rows = [np.asarray(r) for r in ph_rows]
    ph_frac = np.asarray(ph_frac, dtype=np.float64)
    if perms is not None:
        perms = [np.asarray(p) for p in perms]
    key = (tuple(r.tobytes() for r in ph_rows), ph_frac.tobytes(),
           len(groups), nat.shape[1],
           None if perms is None else tuple(p.tobytes() for p in perms))
    fn = _WINDOW_CACHE.get(key)
    if fn is None:
        fn = _make_window(ph_rows, ph_frac, len(groups), perms=perms)
        _WINDOW_CACHE[key] = fn
    with jax.enable_x64(True):
        times, got, got_g = jax.device_get(
            fn(nat, deliv, np.float64(budget_us),
               [gd for gd, _ in groups]))
    fracs = np.asarray(got) / tot_sum
    g_fracs = [engine_mod._tier_frac(np.asarray(gg), gt.sum(axis=1))
               for gg, (_, gt) in zip(got_g, groups)]
    return np.asarray(times), fracs, g_fracs
