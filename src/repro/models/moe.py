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
  every routed row is computed.  Both permutes are gathers whose
  backward is a gather by the inverse permutation (custom VJPs), and
  the indices come from sorts and a one-hot count: no scatter runs,
  forward or backward.  With no mesh set the grouped matmuls
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

DeepSeek-V3's block adds three things (``MoEConfig``): sigmoid routing
(``score_func``) whose top-k choice adds a per-layer load-balancing
bias that is no gate and takes no gradient (``bias_rate``; the train
step moves it from each layer's routed counts, ``moe_counts``, with
:func:`update_bias`); ``routed_scaling`` on the gates; and a held
share (``n_held``, ``held_first``): the device routes over every
expert but holds and computes only its own, the expert-parallel layer
without its exchange, counted by ``moe_held_rows``.

A held share's routed rows go into a compact buffer of C rows, not G*k:
the sort puts the held experts' rows first, grouped by expert, and only
its first C rows are gathered, run through the experts and read back by
the combine, whose backward takes each gate's cotangent per buffer row.
C is derived from shapes (:func:`_buffer_rows`): twice the held share's
expected rows, ``2 * G*k * n_held / n_experts``, rounded up to the
grouped matmul's row tile and at most G*k; the deployment's
expert-parallel exchange would deliver about that share to the chip.
Where the held rows do not fit (``sum(sizes) > C``, data-dependent), a
``jax.lax.cond`` runs the tokens in chunks instead, each sorted alone
into a buffer of all its picks (at most C) and under ``jax.checkpoint``,
so that no full-size buffer is built or kept for the backward; the
counter ``moe_held_overflow`` counts the layer calls that took it.  No
row is dropped either way.

Routed experts are zero-padded to ``MoEConfig.expert_pad_multiple``
(16 keeps parameter shapes mesh-independent up to a 16-way model axis;
dummy experts are unroutable: router logits forced to -inf).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.configs.base import ModelConfig
from repro.kernels import ops

Params = Dict[str, Any]

# how each counter combines over layers, microbatches and dp shards;
# ``moe_held_rows`` (a held share only: the routed rows of the held
# experts, each layer's over the MoE layers, so their mean) and
# ``moe_held_overflow`` (the layer calls whose held rows overflowed the
# compact buffer, so ran in token chunks)
STATS = {"moe_load_max": "max", "moe_dropped": "sum"}
HELD_STATS = {"moe_held_rows": "sum", "moe_held_overflow": "sum"}
# per-layer outputs of a block, stacked over the MoE layers and not
# merged: each token's expert ids (``routes``), and with a router bias
# each routed expert's count of the layer's routed rows (summed over
# microbatches and dp shards)
PER_LAYER = ("moe_routes", "moe_counts")
_HOW = {**STATS, **HELD_STATS, "moe_counts": "sum"}


def padded_experts(cfg: ModelConfig) -> int:
    """Routed experts routed over, padded to
    ``cfg.moe.expert_pad_multiple``."""
    e, m = cfg.moe.n_experts, cfg.moe.expert_pad_multiple
    return -(-e // m) * m


def held(cfg: ModelConfig) -> tuple[int, int] | None:
    """(first, count) of the routed experts this device holds, or None
    where it holds every one."""
    m = cfg.moe
    if m.n_held is None:
        return None
    if not 0 <= m.held_first <= m.held_first + m.n_held <= m.n_experts:
        raise ValueError(f"experts [{m.held_first}, "
                         f"{m.held_first + m.n_held}) are not among the "
                         f"{m.n_experts} routed")
    return m.held_first, m.n_held


def has_bias(cfg: ModelConfig) -> bool:
    """Whether the MoE layers' routers hold a load-balancing bias."""
    return cfg.moe is not None and cfg.moe.bias_rate > 0


def init_moe(key: jax.Array, cfg: ModelConfig) -> Params:
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    e_pad = padded_experts(cfg)
    share = held(cfg)
    e_here = e_pad if share is None else share[1]
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)

    def tn(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2., 2., shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    p: Params = {
        "router": tn(ks[0], (d, e_pad), d).astype(jnp.float32),
        "wi": tn(ks[1], (e_here, d, f), d),
        "wg": tn(ks[2], (e_here, d, f), d),
        "wo": tn(ks[3], (e_here, f, d), f),
    }
    if has_bias(cfg):
        if cfg.block_pattern != ("moe",):
            raise ValueError("a router bias needs block_pattern ('moe',)")
        if e_pad != m.n_experts:
            raise ValueError("a router bias needs unpadded experts: a "
                             "dummy's would rise by bias_rate every step "
                             "until it is chosen (expert_pad_multiple "
                             f"{m.expert_pad_multiple}, {m.n_experts} "
                             "experts)")
        p["bias"] = jnp.zeros((e_pad,), jnp.float32)
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


def zero_stats(cfg: ModelConfig, counts: bool = False) -> dict:
    """The counters before any layer: none for a model with no MoE;
    ``counts``: also ``moe_counts`` (every MoE layer's, for a step's
    microbatches to sum) where the routers hold a bias."""
    if "moe" not in cfg.block_pattern:
        return {}
    out = {k: jnp.zeros((), jnp.float32)
           for k in {**STATS, **(HELD_STATS if held(cfg) else {})}}
    if counts and has_bias(cfg):
        n_moe = cfg.n_layers - cfg.n_dense_layers
        out["moe_counts"] = jnp.zeros((n_moe, padded_experts(cfg)),
                                      jnp.float32)
    return out


def merge_stats(a: dict, b: dict) -> dict:
    """Two sets of counters as one (``STATS`` says how)."""
    if not a:
        return dict(b)
    return {k: (jnp.maximum if _HOW[k] == "max" else jnp.add)(a[k], b[k])
            for k in a}


def reduce_stats(stats: dict, axes) -> dict:
    """Counters of one shard as those of all ``axes``' shards."""
    return {k: (jax.lax.pmax if _HOW[k] == "max" else jax.lax.psum)(
        v, axes) for k, v in stats.items()}


def split_bias(params: Params) -> tuple[Params, Params]:
    """(weights, biases): ``params`` with every router bias as None, and
    the router biases alone (None elsewhere).  Without a bias the
    weights are ``params`` itself."""
    def is_bias(path):
        keys = [getattr(k, "key", None) for k in path[-2:]]
        return keys == ["moe", "bias"]

    weights = jax.tree_util.tree_map_with_path(
        lambda p_, x: None if is_bias(p_) else x, params)
    biases = jax.tree_util.tree_map_with_path(
        lambda p_, x: x if is_bias(p_) else None, params)
    return weights, biases


def join_bias(weights: Params, biases: Params) -> Params:
    """The inverse of :func:`split_bias`."""
    return jax.tree.map(lambda w, b: b if w is None else w, weights, biases,
                        is_leaf=lambda x: x is None)


def update_bias(biases: Params, counts: jax.Array,
                cfg: ModelConfig) -> Params:
    """DeepSeek-V3's auxiliary-loss-free balance: each MoE layer's bias
    ``b_e += bias_rate * sign(mean(c) - c_e)``, ``counts`` (L, E) the
    step's routed rows of every expert of each MoE layer in order."""
    rate = cfg.moe.bias_rate

    def one(b):
        return b + rate * jnp.sign(
            jnp.mean(counts, -1, keepdims=True) - counts).reshape(b.shape)
    return jax.tree.map(one, biases)


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
    if m.score_func == "sigmoid":
        return _route_sigmoid(p, cfg, logits, e_pad)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)
    ce = jnp.zeros((e_pad,)).at[top_i.reshape(-1)].add(1.0) / top_i.size
    aux = (m.aux_weight * e_pad * jnp.sum(me * ce)
           + m.router_z_weight * jnp.mean(
               jnp.square(jax.nn.logsumexp(logits, axis=-1))))
    return top_p, top_i, aux, ce


def _route_sigmoid(p: Params, cfg: ModelConfig, logits: jax.Array,
                   e_pad: int):
    """DeepSeek-V3's router over ``logits`` (G, e_pad): scores
    ``sigmoid(logits)``; the top k of the scores plus the layer's bias
    (where it holds one: for the choice only, outside the gradient);
    gates the chosen scores, renormalised over the k, times
    ``routed_scaling``.  No auxiliary loss."""
    m = cfg.moe
    scores = jax.nn.sigmoid(logits)
    choice = scores
    if "bias" in p:
        choice = scores + jax.lax.stop_gradient(p["bias"])
    top_i = jax.lax.top_k(choice, m.top_k)[1]
    top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20) * m.routed_scaling
    return (top_p, top_i, jnp.zeros((), jnp.float32),
            _counts(top_i, e_pad) / top_i.size)


def _counts(top_i: jax.Array, e_pad: int) -> jax.Array:
    """Each expert's routed rows (e_pad,) in float32, counted without a
    scatter."""
    return jnp.sum(top_i.reshape(-1)[:, None]
                   == jnp.arange(e_pad, dtype=top_i.dtype), axis=0,
                   dtype=jnp.float32)


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


def _sort_by_expert(flat: jax.Array, e_pad: int):
    """(G*k,) expert ids -> (order, back, sizes): the stable sort of the
    routed rows by expert, its inverse permutation and each expert's
    row count, with no scatter."""
    order = jnp.argsort(flat, stable=True)     # rows grouped by expert
    back = jnp.argsort(order)                  # back[order[i]] = i
    sizes = jnp.sum(flat[:, None] == jnp.arange(e_pad, dtype=flat.dtype),
                    axis=0, dtype=jnp.int32)
    return order, back, sizes


def _buffer_rows(cfg: ModelConfig, routed: int) -> int:
    """C, the rows of a held share's compact buffer for ``routed`` (G*k)
    routed rows: twice the held experts' expected rows,
    ``routed * n_held / n_experts``, rounded up to the grouped matmul's
    row tile, and at most ``routed``."""
    m = cfg.moe
    twice = -(-2 * routed * m.n_held // m.n_experts)
    return min(routed, -(-twice // ops.GMM_TILE) * ops.GMM_TILE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x2d, order, back, k, keep=None):
    """Each token's row once per pick, grouped by expert:
    ``x2d[order // k]`` (len(order), d).  Its backward gathers the rows'
    cotangents back to token order by ``back`` and sums each token's k
    in float32, where JAX would transpose the gather to a scatter-add.
    ``keep`` (G, k), where given, zeroes the picks it leaves out: rows
    no matmul computed, and rows past the first C of the sort where
    ``order`` is cut to them (their ``back`` is clamped to the buffer)."""
    return jnp.take(x2d, order // k, axis=0)


def _dispatch_rows_fwd(x2d, order, back, k, keep=None):
    return _dispatch_rows(x2d, order, back, k, keep), (back, keep)


def _dispatch_rows_bwd(k, res, drows):
    back, keep = res
    g = back.shape[0] // k
    if keep is None:
        dx = jnp.take(drows, back, axis=0).reshape(g, k, -1)
    else:
        dx = jnp.take(drows, back, axis=0, mode="clip").reshape(g, k, -1)
        dx = jnp.where(keep[..., None], dx, 0)
    return dx.astype(jnp.float32).sum(1).astype(drows.dtype), None, None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(out, top_p, order, back, keep=None):
    """The experts' rows ``out``, in ``order``'s order, back in token
    order, summed with their gates ``top_p`` (G, k) in float32: (G, d)
    in out's dtype.  Its backward gathers too: row ``i``'s cotangent is
    its token's, ``order[i] // k``, times its gate, and each gate's is
    its row's dot with its token's.  ``keep`` (G, k), where given,
    zeroes the picks it leaves out: rows no matmul computed, whatever
    they hold, and rows past ``out``'s, where ``out`` and ``order`` are
    cut to the first C rows of the sort (``back`` is clamped to them).
    The backward then takes each gate's dot per row of ``out`` and
    gathers it to the picks, so it keeps no (G, k, d) array."""
    return _combine_rows_fwd(out, top_p, order, back, keep)[0]


def _combine_rows_fwd(out, top_p, order, back, keep=None):
    g, k = top_p.shape
    if keep is None:
        got = jnp.take(out, back, axis=0).reshape(g, k, -1)
        res = (got, top_p, order, None, None)
    else:
        got = jnp.take(out, back, axis=0, mode="clip").reshape(g, k, -1)
        got = jnp.where(keep[..., None], got, 0)
        top_p = jnp.where(keep, top_p, 0.0)
        res = (out, top_p, order, back, keep)
    y = jnp.einsum("gkd,gk->gd", got.astype(jnp.float32), top_p)
    return y.astype(out.dtype), res


def _combine_rows_bwd(res, dy):
    saved, top_p, order, back, keep = res
    k = top_p.shape[1]
    gate = jnp.take(top_p.reshape(-1), order)
    dy_rows = jnp.take(dy, order // k, axis=0).astype(jnp.float32)
    d_out = dy_rows * gate[:, None]
    if keep is None:   # saved: the (G, k, d) rows in token order
        d_top_p = jnp.einsum("gkd,gd->gk", saved.astype(jnp.float32),
                             dy.astype(jnp.float32))
    else:              # saved: out itself
        dots = jnp.sum(saved.astype(jnp.float32) * dy_rows, -1)
        d_top_p = jnp.where(keep, jnp.take(dots, back, mode="clip")
                            .reshape(top_p.shape), 0.0)
    return d_out.astype(saved.dtype), d_top_p, None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _expert_rows(c, k, grouped, x2d, top_p, order, back, keep, sizes, w):
    """The routed rows through their experts, in a buffer of the first
    ``c`` rows of the sort (``order``), which has to hold every row a
    matmul computes (``sum(sizes) <= c``); ``keep`` as the permutes
    take it: (G, d) in x2d's dtype."""
    with jax.named_scope("dispatch"):
        order = order[:c]
        rows = _dispatch_rows(x2d, order, back, k, keep)        # (c, d)
    with jax.named_scope("experts"):
        a = grouped(rows, w["wg"], sizes)
        b = grouped(rows, w["wi"], sizes)
        out = grouped(jax.nn.silu(a) * b, w["wo"], sizes)
    with jax.named_scope("combine"):
        return _combine_rows(out, top_p, order, back, keep)


def _held_sort(top_i, held_share):
    """The held share's sort of the routed rows: (keep (G, k), order,
    back, sizes of the held experts); absent experts sort last, as one
    group no matmul visits."""
    first, n_here = held_share
    local = top_i - first
    keep = (local >= 0) & (local < n_here)
    order, back, sizes = _sort_by_expert(
        jnp.where(keep, local, n_here).reshape(-1), n_here + 1)
    return keep, order, back, sizes[:n_here]


def _held_in_chunks(c, k, grouped, held_share, w, x2d, top_p, top_i):
    """The held share's rows where they overflow a compact buffer of
    ``c``: the tokens in chunks of t, the most that divides G with
    t*k <= c, each sorted alone into a buffer of all its t*k picks, so
    that none can overflow.  Each chunk runs under ``jax.checkpoint``,
    so the backward keeps only the chunks' inputs and holds one chunk's
    rows at a time: (G, d)."""
    g = x2d.shape[0]
    t = max(n for n in range(1, c // k + 1) if g % n == 0)

    @jax.checkpoint
    def chunk(a):
        xc, pc, ic = a
        with jax.named_scope("dispatch"):
            keep, order, back, sizes = _held_sort(ic, held_share)
        return _expert_rows(t * k, k, grouped, xc, pc, order, back, keep,
                            sizes, w)

    return jax.lax.map(chunk, (x2d.reshape(g // t, t, -1),
                               top_p.reshape(g // t, t, k),
                               top_i.reshape(g // t, t, k))
                       ).reshape(x2d.shape)


def _moe_dropless(p, cfg, x2d, e_pad, grouped, routes: bool = False):
    """Every routed row through its expert, grouped by expert with
    ``grouped(rows, weights, sizes)``.  Returns (out (G, d),
    aux, stats).

    With a held share (``held``) only the rows routed to the held
    experts are computed: the sort puts them first, grouped by expert,
    and the rows of absent experts after them.  Only the first C
    (:func:`_buffer_rows`) are gathered and run through the experts,
    whose ``sizes`` cover the held rows alone; the combine and the
    dispatch's backward mask the rest to zero.  Where the held rows
    exceed C, ``jax.lax.cond`` runs them in token chunks instead
    (:func:`_held_in_chunks`)."""
    k = cfg.moe.top_k
    held_share = held(cfg)
    w = {n: p[n] for n in ("wg", "wi", "wo")}
    with jax.named_scope("route"):
        top_p, top_i, aux, share = _route(p, cfg, x2d, e_pad)
    routed = top_i.size
    if held_share is None:
        with jax.named_scope("dispatch"):
            order, back, sizes = _sort_by_expert(top_i.reshape(-1), e_pad)
        y = _expert_rows(routed, k, grouped, x2d, top_p, order, back, None,
                         sizes, w)
    else:
        with jax.named_scope("dispatch"):
            keep, order, back, sizes = _held_sort(top_i, held_share)
        c = _buffer_rows(cfg, routed)

        def compact(*_):
            return _expert_rows(c, k, grouped, x2d, top_p, order, back, keep,
                                sizes, w)

        if c == routed:
            overflow, y = jnp.zeros((), bool), compact()
        else:
            overflow = jnp.sum(sizes) > c
            y = jax.lax.cond(overflow, functools.partial(
                _held_in_chunks, c, k, grouped, held_share, w), compact,
                x2d, top_p, top_i)
    stats = {"moe_load_max": _load_max(cfg, share),
             "moe_dropped": jnp.zeros((), jnp.float32)}
    if held_share is not None:
        n_moe = cfg.n_layers - cfg.n_dense_layers
        stats["moe_held_rows"] = jnp.sum(keep, dtype=jnp.float32) / n_moe
        stats["moe_held_overflow"] = overflow.astype(jnp.float32)
    if "bias" in p:
        stats["moe_counts"] = _counts(top_i, e_pad)
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
    if "bias" in p:   # each sender's share is its counts over G_loc * k
        stats["moe_counts"] = jnp.round(
            shares.sum(0) * (b * s_loc * cfg.moe.top_k))
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
            if held(cfg) is not None:
                raise ValueError("a held share runs the dropless path "
                                 "only: the expert-parallel exchange that "
                                 "completes it is not here")
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
