"""Mixture-of-Experts block: dropless on one device, expert parallel
(EP) over the model axis.

Design (TPU/XLA-friendly — every shape static):

- router: top-k over routed experts (softmax, top-k, renormalised over
  the k: the same gates as a softmax over the top-k logits), its matmul
  in float32 at HIGHEST precision (tiny: d x E); load-balance and router-z
  auxiliary losses; optional always-on shared experts as a dense MLP.
- one device (and decode): **dropless**.  The G*k routed rows are
  sorted by expert (a stable sort, so each expert's rows keep token
  order), gathered, and each expert's contiguous group runs through its
  SwiGLU as grouped matmuls with the per-expert row counts; the rows are
  put back in token order and summed with their gates.  No capacity:
  every routed row is computed.  With no mesh set the grouped matmuls
  are ``kernels.ops.grouped_matmul`` (the Pallas megablox kernel); on a
  mesh (a dp-only mesh, the dp-manual train island, decode on a
  model-sharded mesh) they are ``jax.lax.ragged_dot``, plain XLA that
  GSPMD partitions, because a Mosaic kernel cannot be partitioned
  automatically.
- EP (train/prefill on a mesh with a model axis) is capacity-based and
  runs in **pure GSPMD form** (works inside the dp-manual Celeris train
  island, where a nested manual shard_map over 'model' is illegal): the
  sequence axis folds into a leading "sender shard" dim constrained onto
  the model axis, per-sender dispatch runs under vmap (each pick gets a
  slot in a per-expert capacity buffer from a cumsum over its one-hot;
  overflow is dropped), and the (TP,E,..) -> (E,TP,..) resharding
  constraint lowers to the EP all-to-all.  With Celeris enabled,
  dispatch is *lossy*: a (sender, expert-shard) block that misses the
  bounded window is dropped before the reshard — the expert sees zeros
  (swiglu(0)=0) and those tokens fall back to the shared-expert/residual
  path (paper §II-B "expert fallback paths").

The block runs under the ``moe`` name scope, with children ``route``
(router and aux losses), ``dispatch`` (sort and gather; capacity slots
on EP), ``experts`` (the expert matmuls) and ``combine`` (back to token
order, gate-weighted sum).  :func:`moe_block` also returns two
counters: ``moe_load_max``, the busiest expert's routed rows over the
mean, and ``moe_dropped``, the routed rows dropped (0 on the dropless
path; capacity overflow on EP).

Routed experts are zero-padded to ``MoEConfig.expert_pad_multiple``
(16 keeps parameter shapes mesh-independent up to a 16-way model axis;
dummy experts are unroutable: router logits forced to -inf).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.configs.base import ModelConfig
from repro.kernels import ops

Params = Dict[str, Any]

# how each counter combines over layers, microbatches and dp shards
STATS = {"moe_load_max": "max", "moe_dropped": "sum"}


def padded_experts(cfg: ModelConfig) -> int:
    """Routed experts held, padded to ``cfg.moe.expert_pad_multiple``."""
    e, m = cfg.moe.n_experts, cfg.moe.expert_pad_multiple
    return -(-e // m) * m


def init_moe(key: jax.Array, cfg: ModelConfig) -> Params:
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    e_pad = padded_experts(cfg)
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)

    def tn(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2., 2., shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    p: Params = {
        "router": tn(ks[0], (d, e_pad), d).astype(jnp.float32),
        "wi": tn(ks[1], (e_pad, d, f), d),
        "wg": tn(ks[2], (e_pad, d, f), d),
        "wo": tn(ks[3], (e_pad, f, d), f),
    }
    if m.n_shared:
        fs = m.n_shared * m.d_expert
        p["shared"] = {
            "wi": tn(ks[4], (d, fs), d),
            "wg": tn(jax.random.fold_in(ks[4], 1), (d, fs), d),
            "wo": tn(jax.random.fold_in(ks[4], 2), (fs, d), fs),
        }
    return p


def param_specs(cfg: ModelConfig) -> Params:
    """PartitionSpecs for MoE params (experts sharded over model)."""
    specs: Params = {
        "router": P(),
        "wi": P(shd.MODEL_AXIS, None, None),
        "wg": P(shd.MODEL_AXIS, None, None),
        "wo": P(shd.MODEL_AXIS, None, None),
    }
    if cfg.moe and cfg.moe.n_shared:
        specs["shared"] = {"wi": P(None, shd.MODEL_AXIS),
                           "wg": P(None, shd.MODEL_AXIS),
                           "wo": P(shd.MODEL_AXIS, None)}
    return specs


def zero_stats(cfg: ModelConfig) -> dict:
    """The counters before any layer: none for a model with no MoE."""
    if "moe" not in cfg.block_pattern:
        return {}
    return {k: jnp.zeros((), jnp.float32) for k in STATS}


def merge_stats(a: dict, b: dict) -> dict:
    """Two sets of counters as one (``STATS`` says how)."""
    if not a:
        return dict(b)
    return {k: (jnp.maximum if STATS[k] == "max" else jnp.add)(a[k], b[k])
            for k in a}


def reduce_stats(stats: dict, axes) -> dict:
    """Counters of one shard as those of all ``axes``' shards."""
    return {k: (jax.lax.pmax if STATS[k] == "max" else jax.lax.psum)(
        v, axes) for k, v in stats.items()}


def _capacity(cfg: ModelConfig, g_tokens: int) -> int:
    m = cfg.moe
    c = int(g_tokens * m.top_k * m.capacity_factor) // padded_experts(cfg)
    return max(8, -(-c // 8) * 8)   # round up to 8 for TPU tiling


def _route(p: Params, cfg: ModelConfig, x2d: jax.Array, e_pad: int):
    """Top-k routing.  x2d: (G, d) -> (gates (G,k), ids (G,k), aux loss,
    each expert's share of the G*k routed rows (e_pad,))."""
    m = cfg.moe
    logits = jnp.dot(x2d.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    if e_pad > m.n_experts:   # dummy padded experts are unroutable
        pad_mask = jnp.arange(e_pad) >= m.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)
    ce = jnp.zeros((e_pad,)).at[top_i.reshape(-1)].add(1.0) / top_i.size
    aux = (m.aux_weight * e_pad * jnp.sum(me * ce)
           + m.router_z_weight * jnp.mean(
               jnp.square(jax.nn.logsumexp(logits, axis=-1))))
    return top_p, top_i, aux, ce


def _load_max(cfg: ModelConfig, share: jax.Array) -> jax.Array:
    """The busiest expert's routed rows over the mean expert's."""
    return jnp.max(share) * cfg.moe.n_experts


def _dispatch_indices(top_i: jax.Array, e_pad: int, cap: int):
    """Slot assignment: (G,k) expert ids -> (flat ids, slots, keep mask)."""
    flat = top_i.reshape(-1)                                   # (G*k,)
    onehot = jax.nn.one_hot(flat, e_pad, dtype=jnp.int32)      # (G*k, E)
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    slot = pos.sum(-1)                                         # (G*k,)
    keep = slot < cap
    return flat, slot, keep


def _expert_ffn(wi, wg, wo, h: jax.Array) -> jax.Array:
    """h: (E_local, C_total, d) -> same; batched swiglu per expert."""
    a = jnp.einsum("ecd,edf->ecf", h, wg)
    b = jnp.einsum("ecd,edf->ecf", h, wi)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(a) * b, wo)


def _scatter_combine(x2d, top_p, flat, slot, keep, out_buf, cap):
    """Gather expert outputs back to token order, weighted by router."""
    g, d = x2d.shape
    k = top_p.shape[-1]
    got = out_buf[flat, jnp.minimum(slot, cap - 1)]
    got = got * (keep[:, None] * top_p.reshape(-1)[:, None]).astype(got.dtype)
    return got.reshape(g, k, d).sum(1)


def _moe_dropless(p, cfg, x2d, e_pad, grouped, routes: bool = False):
    """Every routed row through its expert, grouped by expert with
    ``grouped(rows, weights, sizes)``.  Returns (out (G, d),
    aux, stats)."""
    g, d = x2d.shape
    k = cfg.moe.top_k
    with jax.named_scope("route"):
        top_p, top_i, aux, share = _route(p, cfg, x2d, e_pad)
    with jax.named_scope("dispatch"):
        flat = top_i.reshape(-1)                               # (G*k,)
        order = jnp.argsort(flat, stable=True)     # rows grouped by expert
        sizes = jnp.bincount(flat, length=e_pad)
        rows = jnp.take(x2d, order // k, axis=0)               # (G*k, d)
    with jax.named_scope("experts"):
        a = grouped(rows, p["wg"], sizes)
        b = grouped(rows, p["wi"], sizes)
        out = grouped(jax.nn.silu(a) * b, p["wo"], sizes)
    with jax.named_scope("combine"):
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        got = jnp.take(out, back, axis=0).reshape(g, k, d)
        y = jnp.einsum("gkd,gk->gd", got.astype(jnp.float32), top_p)
    stats = {"moe_load_max": _load_max(cfg, share),
             "moe_dropped": jnp.zeros((), jnp.float32)}
    if routes:
        stats["moe_routes"] = top_i
    return y.astype(x2d.dtype), aux, stats


def _dispatch_2d(p, cfg, x2d, e_pad, cap, src_mask=None):
    """Route+dispatch a (G,d) token block into (E,C,d) capacity buffers.

    ``src_mask`` (E,) optional arrival mask for this sender's blocks
    (Celeris lossy dispatch: tokens bound for a dropped (sender, expert-
    shard) block never arrive; swiglu(0)=0 so they contribute nothing
    and fall back to shared-expert/residual).
    Returns (buf, combine_fn, aux, each expert's share of the routed
    rows, routed rows over capacity).
    """
    g, d = x2d.shape
    k = cfg.moe.top_k
    with jax.named_scope("route"):
        top_p, top_i, aux, share = _route(p, cfg, x2d, e_pad)
    with jax.named_scope("dispatch"):
        flat, slot, keep = _dispatch_indices(top_i, e_pad, cap)
        dropped = jnp.sum(~keep).astype(jnp.float32)
        if src_mask is not None:
            keep = keep & src_mask[flat]
        rows = jnp.repeat(x2d, k, axis=0) * keep[:, None].astype(x2d.dtype)
        buf = jnp.zeros((e_pad, cap, d), x2d.dtype)
        buf = buf.at[flat, jnp.minimum(slot, cap - 1)].add(rows)

    def combine(out_buf):
        with jax.named_scope("combine"):
            return _scatter_combine(x2d, top_p, flat, slot, keep, out_buf,
                                    cap)

    return buf, combine, aux, share, dropped


def _constrain(x, spec):
    mesh = shd.get_global_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _moe_ep_gspmd(p, cfg, x, e_pad, tp, lossy, key, drop_rate):
    """Expert parallelism in pure GSPMD (auto) form.

    The sequence axis is folded into a leading "sender shard" dim that
    rides the model axis; per-sender dispatch runs under vmap (batched
    scatters partition cleanly), and the (TP,E,..) -> (E,TP,..)
    resharding constraint lowers to the EP all-to-all.  Works both
    inside the dp-manual train island and in plain serving jits.
    Returns (out, aux, stats).
    """
    b, s, d = x.shape
    s_loc = s // tp
    xs = x.reshape(b, tp, s_loc, d).swapaxes(0, 1).reshape(tp, b * s_loc, d)
    xs = _constrain(xs, P(shd.MODEL_AXIS, None, None))
    cap = _capacity(cfg, b * s_loc)

    if lossy:
        # (sender, dest-shard) arrival coins -> expand to (sender, expert)
        key = key if key is not None else jax.random.PRNGKey(0)
        coins = jax.random.uniform(key, (tp, tp)) >= drop_rate
        src_masks = jnp.repeat(coins, e_pad // tp, axis=1)     # (TP, E)
    else:
        src_masks = jnp.ones((tp, e_pad), bool)

    def one_sender(x2d, mask):
        buf, _, aux, share, dropped = _dispatch_2d(p, cfg, x2d, e_pad, cap,
                                                   src_mask=mask)
        return buf, aux, share, dropped

    bufs, auxs, shares, dropped = jax.vmap(one_sender)(xs, src_masks)
    bufs = _constrain(bufs, P(shd.MODEL_AXIS, None, None, None))

    # ---- EP "all-to-all": reshard sender-major -> expert-major
    h = bufs.swapaxes(0, 1)                            # (E,TP,C,d)
    h = _constrain(h, P(shd.MODEL_AXIS, None, None, None))
    h = h.reshape(e_pad, tp * cap, d)
    with jax.named_scope("experts"):
        out = _expert_ffn(p["wi"], p["wg"], p["wo"], h)    # E-sharded
    out = _constrain(out, P(shd.MODEL_AXIS, None, None))

    # ---- return path
    back = out.reshape(e_pad, tp, cap, d).swapaxes(0, 1)
    back = _constrain(back, P(shd.MODEL_AXIS, None, None, None))

    def one_receiver(x2d, mask, out_buf):
        # recompute indices (cheap) to combine; same routing as dispatch
        _, combine, _, _, _ = _dispatch_2d(p, cfg, x2d, e_pad, cap,
                                           src_mask=mask)
        return combine(out_buf)

    ys = jax.vmap(one_receiver)(xs, src_masks, back)   # (TP, B*S_loc, d)
    ys = _constrain(ys, P(shd.MODEL_AXIS, None, None))
    y = ys.reshape(tp, b, s_loc, d).swapaxes(0, 1).reshape(b, s, d)
    stats = {"moe_load_max": _load_max(cfg, shares.mean(0)),
             "moe_dropped": jnp.sum(dropped)}
    return y, auxs.mean(), stats


def moe_block(p: Params, cfg: ModelConfig, x: jax.Array, *,
              lossy: bool = False,
              key: Optional[jax.Array] = None,
              drop_rate: jax.Array | float = 0.0,
              routes: bool = False,
              ) -> tuple[jax.Array, jax.Array, dict]:
    """x: (B, S, d) -> (out, aux_loss, stats).  Adds shared-expert output.

    ``stats`` holds the counters of ``STATS``; with ``routes`` (off the
    expert-parallel path) also ``moe_routes``, each token's expert ids
    (B*S, k).
    """
    mesh = shd.get_global_mesh()
    tp = mesh.shape[shd.MODEL_AXIS] if mesh is not None else 1
    e_pad = padded_experts(cfg)
    b, s, d = x.shape

    with jax.named_scope("moe"):
        if mesh is None or tp == 1 or s % tp or s < tp:
            # dropless local dispatch: one device, a dp-only mesh, or
            # decode; on a mesh GSPMD partitions the ragged dots
            grouped = (ops.grouped_matmul if mesh is None
                       else jax.lax.ragged_dot)
            routed, aux, stats = _moe_dropless(p, cfg, x.reshape(-1, d),
                                               e_pad, grouped, routes=routes)
            routed = routed.reshape(b, s, d)
        else:
            if e_pad % tp:
                raise ValueError(
                    f"{e_pad} experts do not split over a {tp}-way model "
                    "axis: raise MoEConfig.expert_pad_multiple")
            if routes:
                raise ValueError("routes are not collected on the "
                                 "expert-parallel path")
            routed, aux, stats = _moe_ep_gspmd(
                p, cfg, x, e_pad, tp, lossy, key,
                jnp.asarray(drop_rate, jnp.float32))

        if "shared" in p:
            sp = p["shared"]
            shared = (jax.nn.silu(x @ sp["wg"]) * (x @ sp["wi"])) @ sp["wo"]
            routed = routed + shared
    return routed, aux, stats
