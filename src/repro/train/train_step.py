"""Train-step factory: GSPMD TP/SP + transport-coupled gradient sync.

The gradient collective dispatches on
:class:`repro.core.transport.coupling.CollectiveMode`
(``CelerisConfig.mode``):

- **exact** — lossless all-reduce (RoCE-like semantics).  On a mesh
  this is pure GSPMD: the batch is dp-sharded and value_and_grad of the
  global batch-mean loss makes the partitioner insert the all-reduces.
- **lossy** — best-effort WITHOUT coding, the Fig.-1 ablation: a
  shard_map island like the coded one, with per-(peer, wire-row) masks
  applied *before* the plain psum — true sender-side loss, no recovery
  and no rescaling (:func:`_sync_grads_plain_island`).  Without a dp
  axis the single device applies one receiver-window mask per leaf
  (:func:`_mask_grads_plain`).
- **lossy_hadamard** — the paper's §III-B path, a **shard_map island,
  manual over the dp axes ('pod','data'), auto (GSPMD) over 'model'**:
  each dp shard runs value_and_grad on its local batch, then per-leaf
  randomized-Hadamard encode (wire-interleaved), per-(peer, wire-row)
  arrival masks drawn from the step's drop probability (fed by the
  transport engine through ``coupling.DropSchedule``), count-unbiased
  decode.  The realized received fraction is returned for the timeout
  controller.  Sharding hint: rotation blocks ride the 'model' axis so
  the FWHT is collective-free and nothing de-shards.
- **hierarchical** — the multi-pod topology split: gradients first
  reduce *exactly* over the intra-pod 'data' axis (the fat in-pod
  fabric is effectively lossless), then the pod-mean gradients take
  the best-effort + Hadamard path over the 'pod' axis only — arrival
  masks are per-(pod, wire-row) at the DCI tier's drop rate.  The
  step's ``drop_rate`` input is the axis vector produced by
  ``coupling.AxisSchedules`` / ``HierStragglerModel``: the ``(2,)``
  aggregate ``[intra, cross]`` consumes ``drop_rate[-1]``; the per-pod
  ``(n_pods + 1,)`` form ``[intra_pod0..., cross]`` charges each pod's
  mask the combined rate ``1 - (1 - intra_pod)(1 - cross)`` (the shard
  rides its pod fabric before the DCI exchange).
  This sync order mirrors the transport engine's
  ``schedule.HierarchicalSchedule`` phase plan — intra-pod
  reduce-scatter, then the lossy cross-pod DCI exchange, then
  intra-pod all-gather — and ``make_train_step`` asserts against its
  ``PHASE_ORDER`` so the two layers cannot drift apart silently.
  Composing ``quantize_wire=True`` with this mode quantizes *only* the
  cross-pod shards: the intra-pod pmean runs before the coded island's
  encode/quantize stage, so in-pod sync stays full-precision f32 while
  the DCI payload ships int8 (the bandwidth-starved hop is the only
  one paying the precision cost).

Then the optimizer update (AdamW, fp32 master, ZeRO-1-sharded state)
under plain GSPMD.  The factory precomputes the per-leaf Hadamard
coding plans from the static param shapes (block counts padded to the
TP degree).

Each layer of the step runs under one ``jax.named_scope``, which XLA
keeps in every HLO op's ``op_name`` metadata, so a device trace can be
split by layer: ``fwd_bwd`` (value_and_grad of the loss, microbatch
accumulation included; backward ops carry ``transpose(jvp(...))``
below it), ``grad_sync`` (every lossy or coded sync, with children
``encode``, ``mask``, ``decode`` and ``psum`` where a collective runs;
on one device a coded leaf's whole rotate-drop-unbias-rotate-back is
one Pallas kernel under ``roundtrip``, with the sign draw under
``encode`` and the mask under ``mask``; the exact GSPMD path has no op
of its own) and ``optimizer``
(``adamw.apply_updates``).  Scopes change op metadata only.  An MoE
model's blocks add ``moe`` inside ``fwd_bwd`` (``repro.models.moe``),
and its step returns their counters (``moe.STATS``: ``moe_load_max``,
``moe_dropped``; with a held share ``moe_held_rows``) among its
metrics.  Where the routers hold a load-balancing bias (DeepSeek-V3's
noaux_tc), the bias sits in the parameters but outside the gradient,
the sync and AdamW: after AdamW, under ``moe_bias``, each layer's is
moved by the step's routed counts over all its experts
(``moe.update_bias``), and ``moe_bias_absmax`` joins the metrics.

The ``drop_rate`` step input is where the transport engine couples in:
``Trainer`` walks an engine-derived ``DropSchedule`` (or the standalone
straggler model) and feeds one scalar per step.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shd
from repro.configs.base import ModelConfig
from repro.core import coding
from repro.core import lossy_collectives as lc
from repro.core.transport.coupling import MAX_DROP, CollectiveMode
from repro.models import model as M
from repro.models import moe as MOE
from repro.optim import adamw
from repro.train import sharding_rules as rules


@dataclasses.dataclass(frozen=True)
class CelerisConfig:
    """Celeris integration knobs for training."""
    mode: str | CollectiveMode = "exact"
                                     # "exact" | "lossy" | "lossy_hadamard"
                                     # | "hierarchical".  "lossy" is the
                                     # uncoded ablation: dropped wire rows
                                     # stay dropped, so the Fig.-1 A/B
                                     # isolates what the Hadamard layer
                                     # buys.  "hierarchical" needs a 'pod'
                                     # mesh axis and a (2,) [intra, cross]
                                     # drop input (coupling.AxisSchedules).
    lossy_moe: bool = False          # lossy expert-parallel All-to-All
    n_rot: int = 4096                # Hadamard rotation width
    min_coded_size: int = 65536      # leaves smaller than this sync exactly
    wire_dtype: str = "float32"      # collective payload dtype.  H3: set
                                     # "bfloat16" on TPU to halve DP sync
                                     # bytes (decode stays f32).  Default
                                     # f32: XLA *CPU*'s AllReducePromotion
                                     # pass crashes on mixed-dtype variadic
                                     # all-reduces (see dryrun.py flags).
    quantize_wire: bool = False      # H6 (beyond-paper): int8-quantized
                                     # wire with shared per-row scales,
                                     # summed over dp in int16 -> 2x fewer
                                     # collective bytes than f32 (a sum
                                     # over up to 258 peers fits: 258*127
                                     # < 2^15).  Composes with
                                     # the Hadamard rotation (QSGD-style:
                                     # rotation whitens the per-row range
                                     # so one scale fits all peers).
                                     # Under mode="hierarchical" only the
                                     # cross-pod (DCI) psum is quantized —
                                     # the intra-pod exact pmean happens
                                     # before encode, so in-pod sync stays
                                     # full precision.

    def collective_mode(self) -> CollectiveMode:
        return CollectiveMode.parse(self.mode)


def _pmean32(g, axes):
    """Exact mean over ``axes``, reduced in f32 and cast back: a uniform
    collective dtype (XLA CPU's AllReducePromotion crashes on mixed-dtype
    variadic all-reduce) and better accumulation."""
    with jax.named_scope("psum"):
        return jax.lax.pmean(g.astype(jnp.float32), axes).astype(g.dtype)


def _psum(x, axes):
    with jax.named_scope("psum"):
        return jax.lax.psum(x, axes)


def _dp_size(dp, mesh):
    n_dp = 1
    for ax in dp:
        n_dp *= mesh.shape[ax] if mesh is not None else 1
    return n_dp


def _leaf_mask(key, i, peer_id, n_rot, drop_rate):
    """Per-(leaf, peer) arrival mask.  ``peer_id`` is this shard's index
    along the dp axes (a P(dp)-sharded arange fed into the island); the
    same stream as ``lc.lossy_psum``'s masks."""
    k = jax.random.fold_in(jax.random.fold_in(key, 2 * i + 1), peer_id)
    return lc.arrival_mask(k, n_rot, drop_rate)


def _sync_grads_celeris(grads, dp, plans, key, drop_rate, celeris, mesh,
                        peer_id, lossy_axes=None, exact_axes=()):
    """Per-leaf lossy pmean with Hadamard recovery (sharding-aware ND
    form: rotation runs along each leaf's unsharded axes only, so no
    reshape ever crosses the TP sharding — see coding.encode_nd).

    ``lossy_axes``/``exact_axes`` split the dp group for hierarchical
    topologies: coded leaves first pmean *exactly* over ``exact_axes``
    (intra-pod), then run the lossy coded psum over ``lossy_axes`` only
    (cross-pod), with ``peer_id`` the shard's index along the lossy
    group.  Defaults reproduce the flat behavior (whole dp lossy).
    """
    lossy_axes = tuple(lossy_axes) if lossy_axes is not None else tuple(dp)
    flat, treedef = jax.tree_util.tree_flatten(grads)
    n_lossy = _dp_size(lossy_axes, mesh)
    out, fracs = [], []
    for i, (g, plan) in enumerate(zip(flat, plans)):
        if plan is None:   # small leaf: exact sync
            out.append(_pmean32(g, dp))
            continue
        if exact_axes:     # intra-pod reduction: exact, f32
            with jax.named_scope("psum"):
                g = jax.lax.pmean(g.astype(jnp.float32), exact_axes)
        with jax.named_scope("encode"):
            signs = coding.rademacher_nd(jax.random.fold_in(key, 2 * i),
                                         plan)
        est, counts = lc.lossy_psum(
            g, lossy_axes, plan=plan, signs=signs, key=key, leaf=i,
            peer_id=peer_id,
            drop_rate=drop_rate, quantize_wire=celeris.quantize_wire,
            wire_dtype=celeris.wire_dtype)
        with jax.named_scope("decode"):
            out.append((est / n_lossy).astype(g.dtype))
            fracs.append(jnp.sum(counts) / (n_lossy * plan.n_rot))
    frac = jnp.stack(fracs).mean() if fracs else jnp.float32(1.0)
    return jax.tree_util.tree_unflatten(treedef, out), frac


def _sync_grads_plain_island(grads, dp, plans, key, drop_rate, mesh,
                             peer_id):
    """Per-(peer, wire-row) loss WITHOUT coding, inside the island: each
    peer masks its own contribution *before* the plain psum, so a
    dropped row is missing from that peer only, with no recovery and no
    rescaling — the uncoded sender-side ablation."""
    flat, treedef = jax.tree_util.tree_flatten(grads)
    n_dp = _dp_size(dp, mesh)
    out, fracs = [], []
    for i, (g, plan) in enumerate(zip(flat, plans)):
        if plan is None:
            out.append(_pmean32(g, dp))
            continue
        with jax.named_scope("encode"):
            tiles = coding.to_tiles_nd(g.astype(jnp.float32), plan)
        with jax.named_scope("mask"):
            mask = _leaf_mask(key, i, peer_id, plan.n_rot, drop_rate)
            masked = tiles * mask[None, :, None].astype(tiles.dtype)
        tiles_sum = _psum(masked, dp)
        counts = _psum(mask.astype(jnp.float32), dp)
        with jax.named_scope("decode"):
            out.append(coding.from_tiles_nd(tiles_sum / n_dp, plan)
                       .astype(g.dtype))
            fracs.append(jnp.sum(counts) / (n_dp * plan.n_rot))
    frac = jnp.stack(fracs).mean() if fracs else jnp.float32(1.0)
    return jax.tree_util.tree_unflatten(treedef, out), frac


def _mask_grads_plain(grads, plans, key, drop_rate):
    """Receiver-window loss WITHOUT coding — the Fig.-1 ablation.

    One arrival mask per leaf is applied to the single device's
    gradient: wire rows that miss the bounded window are holes in the
    raw gradient, with no recovery — exactly the damage §III-B's coding
    absorbs.  On a dp mesh the per-(peer, row) form runs in the island
    (:func:`_sync_grads_plain_island`).
    """
    flat, treedef = jax.tree_util.tree_flatten(grads)
    out, fracs = [], []
    for i, (g, plan) in enumerate(zip(flat, plans)):
        if plan is None:
            out.append(g)
            continue
        with jax.named_scope("mask"):
            mask = _leaf_mask(key, i, 0, plan.n_rot, drop_rate)
        with jax.named_scope("encode"):
            tiles = coding.to_tiles_nd(g.astype(jnp.float32), plan)
        with jax.named_scope("mask"):
            masked = tiles * mask[None, :, None].astype(tiles.dtype)
        with jax.named_scope("decode"):
            out.append(coding.from_tiles_nd(masked, plan).astype(g.dtype))
            fracs.append(mask.mean())
    frac = jnp.stack(fracs).mean() if fracs else jnp.float32(1.0)
    return jax.tree_util.tree_unflatten(treedef, out), frac


def _fused(plan) -> bool:
    """Whether a coded leaf's one-device sync is one Pallas kernel
    (``coding.roundtrip_nd``): its tiles are flat rows at least one lane
    (128) wide.  Other coded leaves take ``encode_nd``/``decode_nd``."""
    return plan.sharded_dim is None and plan.n_rot >= 128


def _emulate_coded_one(grads, plans, key, drop_rate):
    """Coded sync on one node: single-peer encode -> receiver-window
    mask -> unbiased decode of each coded leaf, as one Pallas kernel
    where :func:`_fused` holds, else through XLA."""
    flat, treedef = jax.tree_util.tree_flatten(grads)
    out, fracs = [], []
    for i, (g, plan) in enumerate(zip(flat, plans)):
        if plan is None:
            out.append(g)
            continue
        with jax.named_scope("mask"):
            mask = _leaf_mask(key, i, 0, plan.n_rot, drop_rate)
        with jax.named_scope("encode"):
            signs = coding.rademacher_nd(jax.random.fold_in(key, 2 * i),
                                         plan)
        fracs.append(mask.mean())
        if _fused(plan):
            with jax.named_scope("mask"):
                colscale = coding.one_peer_colscale(mask, plan)
            with jax.named_scope("roundtrip"):
                out.append(coding.roundtrip_nd(g, signs, colscale, plan))
            continue
        with jax.named_scope("encode"):
            tiles = coding.encode_nd(g, signs, plan)
        with jax.named_scope("mask"):
            masked = tiles * mask[None, :, None].astype(tiles.dtype)
        with jax.named_scope("decode"):
            est = coding.decode_nd(masked, mask.astype(jnp.float32), signs,
                                   plan, total_peers=1)
            out.append(est.astype(g.dtype))
    frac = jnp.stack(fracs).mean() if fracs else jnp.float32(1.0)
    return jax.tree_util.tree_unflatten(treedef, out), frac


def _leaf_plans(params, celeris: CelerisConfig, mesh) -> list:
    """Each leaf's ND coding plan (rotation along its unsharded axes),
    or None for a leaf small enough to sync exactly."""
    flat = jax.tree_util.tree_leaves(params)
    if mesh is not None:
        flat_specs = jax.tree_util.tree_leaves(
            rules.param_specs(params, mesh),
            is_leaf=lambda x: isinstance(x, P))
    else:
        flat_specs = [P()] * len(flat)

    def sharded_dim(leaf, spec):
        for i, sname in enumerate(spec):
            if sname == shd.MODEL_AXIS and i < leaf.ndim:
                return i
        return None

    return [coding.plan_nd(l.shape, sharded_dim(l, sp), celeris.n_rot)
            if l.size >= celeris.min_coded_size else None
            for l, sp in zip(flat, flat_specs)]


def coded_sync_paths(params, celeris: CelerisConfig, mesh) -> dict:
    """How many coded leaves the step syncs through the one-kernel path
    (``fused``: one device, :func:`_fused`) and how many through XLA
    ``encode_nd``/``decode_nd`` (``xla``); ``params`` may be shapes."""
    plans = [p for p in _leaf_plans(params, celeris, mesh) if p is not None]
    if not celeris.collective_mode().coded:
        return {"fused": 0, "xla": 0}
    fused = 0 if shd.dp_axes(mesh) else sum(map(_fused, plans))
    return {"fused": fused, "xla": len(plans) - fused}


def make_train_step(cfg: ModelConfig, mesh, opt_cfg: adamw.OptConfig,
                    celeris: Optional[CelerisConfig] = None,
                    donate: bool = True, microbatches: int = 1):
    """Returns jitted ``step(state, batch, key, drop_rate) -> (state, metrics)``.

    state = {"params", "opt", "step"}; batch = {"tokens","labels",...}.
    ``microbatches > 1``: gradient accumulation — the local batch is
    split and scanned, dividing activation memory by the count (the
    standard way multi-billion-param train cells fit HBM); the (lossy)
    gradient sync still happens once per step on the accumulated grads.
    """
    celeris = celeris or CelerisConfig()
    mode = celeris.collective_mode()
    dp = shd.dp_axes(mesh)
    if mode is CollectiveMode.HIERARCHICAL and dp and shd.POD_AXIS not in dp:
        raise ValueError(
            "hierarchical collective mode needs a 'pod' mesh axis "
            "(launch.mesh.make_pod_mesh / make_scale_mesh >= 512); "
            f"got dp axes {dp}")
    if mode is CollectiveMode.HIERARCHICAL:
        # contract with the transport engine's collective schedule: the
        # sync below runs exact-intra first ('data' axes), then the
        # coded lossy cross-pod psum ('pod' axis) — the same order as
        # HierarchicalSchedule's phases (rs -> dci -> ag).  If the
        # schedule's phase order ever changes, this mode's sync (and
        # the [intra, cross] drop-vector convention) must change with
        # it, so fail loudly instead of silently mismatching.
        from repro.core.transport.schedule import HierarchicalSchedule
        order = HierarchicalSchedule.PHASE_ORDER
        assert order[0] == "rs" and order[-1] == "ag" and "dci" in order, (
            f"CollectiveMode.HIERARCHICAL assumes intra-reduce -> DCI "
            f"exchange -> intra-gather; HierarchicalSchedule.PHASE_ORDER "
            f"is {order}")
        # priority contract (cut_order="priority" coupling): this mode
        # masks ONLY the cross-pod (DCI) shards — the coded, int8-able,
        # recoverable bytes — so the schedule must place the DCI
        # exchange in the strictly lowest priority class, i.e. the
        # window cuts exactly the bytes the trainer knows how to lose
        # (coupling.PrioritySchedules.low == the masked cross axis).
        prio = HierarchicalSchedule.PRIORITY
        assert prio["dci"] < min(prio["rs"], prio["ag"]), (
            f"CollectiveMode.HIERARCHICAL masks only DCI shards, so the "
            f"DCI phase must be the lowest (cut-first) priority class; "
            f"HierarchicalSchedule.PRIORITY is {prio}")

    bias = MOE.has_bias(cfg)

    @jax.named_scope("fwd_bwd")
    def _grads_one(params, batch, key, drop_rate):
        # the MoE all-to-all coin expects one scalar; hierarchical mode
        # feeds a (2,) [intra, cross] vector — expert exchange crosses
        # pods, so it takes the cross component
        moe_rate = jnp.reshape(drop_rate, (-1,))[-1]
        lossy_ctx = M.LossyCtx(enabled=celeris.lossy_moe, key=key,
                               drop_rate=moe_rate)
        params, biases = MOE.split_bias(params)   # biases: no gradient

        def loss_fn(p):
            return M.lm_loss(MOE.join_bias(p, biases), cfg, batch,
                             lossy=lossy_ctx)

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def _accum_grads(params, batch, key, drop_rate):
        if microbatches > 1:
            with jax.named_scope("fwd_bwd"):
                return _accum_microbatches(params, batch, key, drop_rate)
        (loss, (nll, aux, stats)), grads = _grads_one(params, batch, key,
                                                      drop_rate)
        return loss, nll, aux, stats, grads

    def _accum_microbatches(params, batch, key, drop_rate):
        mb = jax.tree.map(
            lambda a: a.reshape((microbatches,
                                 a.shape[0] // microbatches)
                                + a.shape[1:]), batch)

        def mb_step(carry, xs):
            gacc, lacc, nacc, aacc, sacc = carry
            b_i, i = xs
            (l, (n, a_, s_)), g = _grads_one(
                params, b_i, jax.random.fold_in(key, i), drop_rate)
            gacc = jax.tree.map(
                lambda x, y: x + y.astype(jnp.float32), gacc, g)
            return (gacc, lacc + l, nacc + n, aacc + a_,
                    MOE.merge_stats(sacc, s_)), None

        weights = MOE.split_bias(params)[0]
        g0 = jax.tree.map(
            lambda p_: jnp.zeros(p_.shape, jnp.float32), weights)
        z = jnp.zeros((), jnp.float32)
        (gsum, loss, nll, aux, stats), _ = jax.lax.scan(
            mb_step, (g0, z, z, z, MOE.zero_stats(cfg, counts=True)),
            (mb, jnp.arange(microbatches)))
        inv = 1.0 / microbatches
        grads = jax.tree.map(
            lambda g_, p_: (g_ * inv).astype(p_.dtype), gsum, weights)
        loss, nll, aux = loss * inv, nll * inv, aux * inv
        return loss, nll, aux, stats, grads

    pod_axes = tuple(a for a in dp if a == shd.POD_AXIS)
    data_axes = tuple(a for a in dp if a != shd.POD_AXIS)

    def island(params, batch, key, drop_rate, plans, peer):
        loss, nll, aux, stats, grads = _accum_grads(params, batch, key,
                                                    drop_rate)
        return _island_sync(loss, nll, aux, stats, grads, key, drop_rate,
                            plans, peer)

    @jax.named_scope("grad_sync")
    def _island_sync(loss, nll, aux, stats, grads, key, drop_rate, plans,
                     peer):
        peer_id = peer[0]     # this shard's index along the dp axes
        if mode is CollectiveMode.HIERARCHICAL:
            # intra-pod exact, cross-pod coded-lossy: every data shard
            # in a pod shares the pod's wire, so the mask peer is the
            # pod index and the drop is the cross-pod (DCI) component
            # of the axis vector (scalar inputs work too:
            # reshape(-1)[-1] is the scalar itself).  A per-pod
            # (n_pods + 1,) vector ([intra_pod..., cross], from
            # coupling.AxisSchedules.per_pod) additionally charges each
            # pod's DCI contribution its own pod fabric: the shard
            # rides pod p's intra fabric before the DCI exchange, so
            # its arrival probability is the product of surviving both
            # — rate = 1 - (1 - intra_p)(1 - cross).
            pod_id = peer_id // _dp_size(data_axes, mesh)
            dr = jnp.reshape(drop_rate, (-1,))
            cross = dr[-1]
            n_pods_mesh = _dp_size(pod_axes, mesh)
            if dr.shape[0] == n_pods_mesh + 1 and n_pods_mesh > 1:
                intra_p = jnp.take(dr, pod_id)
                # both components are individually clamped at MAX_DROP
                # by DropSchedule, but their product form can exceed it
                # (up to 0.75) for a heavily faulted pod — hold the
                # combined rate to the same decodability ceiling
                cross = jnp.minimum(
                    1.0 - (1.0 - intra_p) * (1.0 - cross), MAX_DROP)
            grads, frac = _sync_grads_celeris(
                grads, dp, plans, key, cross, celeris, mesh, pod_id,
                lossy_axes=pod_axes, exact_axes=data_axes)
        elif mode is CollectiveMode.LOSSY:
            grads, frac = _sync_grads_plain_island(grads, dp, plans, key,
                                                   drop_rate, mesh, peer_id)
        else:
            grads, frac = _sync_grads_celeris(grads, dp, plans, key,
                                              drop_rate, celeris, mesh,
                                              peer_id)
        with jax.named_scope("psum"):
            loss = jax.lax.pmean(loss, dp)
            nll = jax.lax.pmean(nll, dp)
            aux = jax.lax.pmean(aux, dp)
            stats = MOE.reduce_stats(stats, dp)
        return loss, nll, aux, stats, grads, frac

    def train_step(state, batch, key, drop_rate):
        params = state["params"]
        # the router biases (if any) are no weights: not synced, not
        # AdamW's
        weights, biases = MOE.split_bias(params)
        plans = _leaf_plans(weights, celeris, mesh)

        island_modes = {CollectiveMode.LOSSY_HADAMARD, CollectiveMode.LOSSY}
        if pod_axes:
            island_modes.add(CollectiveMode.HIERARCHICAL)
        use_island = (dp and mode in island_modes
                      and any(p is not None for p in plans))
        if use_island:
            # params/grads are dp-replicated: every in/out spec is P();
            # their 'model' shardings ride through the auto axis.  Each
            # shard's dp index arrives as data (P(dp)-sharded arange)
            # because axis_index doesn't lower under partial-auto.
            rep = jax.tree.map(lambda _: P(), params)
            fn = lambda p_, b_, k_, d_, pe_: island(
                p_, b_, k_, d_, plans, peer=pe_)
            loss, nll, aux, stats, grads, frac = shd.shard_map(
                fn, mesh=mesh,
                in_specs=(rep, rules.batch_specs(mesh, batch), P(), P(),
                          P(dp)),
                out_specs=(P(), P(), P(), P(),
                           jax.tree.map(lambda _: P(), weights), P()),
                axis_names=set(dp), check_vma=False,
            )(params, batch, key, drop_rate,
              jnp.arange(_dp_size(dp, mesh), dtype=jnp.int32))
        elif dp:
            # Exact collectives on a mesh (and lossy modes with no leaf
            # large enough to code) need no manual island: with the
            # batch dp-sharded, value_and_grad of the global batch-mean
            # loss makes GSPMD insert exactly the lossless all-reduces
            # the island's pmean would.
            loss, nll, aux, stats, grads = _accum_grads(params, batch, key,
                                                        drop_rate)
            frac = jnp.float32(1.0)
        else:   # single-device / no-dp path
            (loss, (nll, aux, stats)), grads = _grads_one(params, batch, key,
                                                          drop_rate)
            with jax.named_scope("grad_sync"):
                if mode.coded:
                    # no dp axis to lose data across, but the node itself
                    # still receives only (1 - drop_rate) of each
                    # collective payload inside its bounded window: the
                    # single-peer emulation (what the Fig.-1
                    # loss-tolerance benchmark measures).  Hierarchical
                    # mode loses only on the cross-pod axis, so its
                    # emulation rate is the vector's cross component.
                    grads, frac = _emulate_coded_one(
                        grads, plans, key, jnp.reshape(drop_rate, (-1,))[-1])
                elif mode is CollectiveMode.LOSSY:
                    grads, frac = _mask_grads_plain(grads, plans, key,
                                                    drop_rate)
                else:
                    frac = jnp.float32(1.0)

        new_params, new_opt, om = adamw.apply_updates(
            weights, grads, state["opt"], opt_cfg)
        if bias:
            with jax.named_scope("moe_bias"):
                biases = MOE.update_bias(biases, stats.pop("moe_counts"),
                                         cfg)
                stats["moe_bias_absmax"] = jnp.max(jnp.stack(
                    [jnp.max(jnp.abs(b)) for b in jax.tree.leaves(biases)]))
        new_params = MOE.join_bias(new_params, biases)
        metrics = {"loss": loss, "nll": nll, "aux": aux,
                   "recv_frac": frac, **stats, **om}
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    donate_args = (0,) if donate else ()
    return jax.jit(train_step, donate_argnums=donate_args)


def init_state(key, cfg: ModelConfig):
    params = M.init_params(key, cfg)
    opt = adamw.init_opt_state(MOE.split_bias(params)[0])
    return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}


def state_shardings(state, mesh):
    """NamedShardings for the full train state on ``mesh``."""
    ps = rules.param_shardings(state["params"], mesh)
    return {
        "params": ps,
        "opt": rules.opt_state_shardings(
            state["opt"], MOE.split_bias(state["params"])[0], mesh),
        "step": NamedSharding(mesh, P()),
    }
