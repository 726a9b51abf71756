"""Top-level model assembly.

One unified causal LM core covers all ten assigned architectures via the
config's ``block_pattern``; encoder-decoder (seamless) and modality
frontends (phi-3-vision / seamless stubs) layer on top.

HLO-size discipline: layers are grouped into repeating pattern units and
executed with ``lax.scan`` over *stacked* per-unit parameters (+
``jax.checkpoint`` per unit for remat), so a 42-layer model lowers to a
single rolled loop — essential for compiling 60+ dry-run cells on one
CPU core, and the standard production trick for fast TPU compiles.

Caches mirror the parameter structure (stacked per pattern position) so
serve_step scans over them in lockstep.  Local-attention layers use
modular (ring) KV caches of window size; recurrent layers carry their
own state types.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import xlstm as XL

Params = Dict[str, Any]

ATTN_KINDS = ("global", "local", "moe", "xattn")


@dataclasses.dataclass(frozen=True)
class LossyCtx:
    """Celeris context threaded into collectives inside the model."""
    enabled: bool = False
    key: Optional[jax.Array] = None
    drop_rate: jax.Array | float = 0.0


# ----------------------------------------------------------------------
# Per-block init / apply
# ----------------------------------------------------------------------

def init_block(key: jax.Array, kind: str, cfg: ModelConfig,
               cross: bool = False) -> Params:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: Params = {"ln1": L.init_rmsnorm(d)}
    if kind in ("global", "local", "moe"):
        p["attn"] = L.init_attention(ks[0], cfg)
        p["ln2"] = L.init_rmsnorm(d)
        if kind == "moe":
            p["moe"] = MOE.init_moe(ks[1], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg)
        if cfg.post_norm:
            p["pn1"] = L.init_rmsnorm(d)
            p["pn2"] = L.init_rmsnorm(d)
        if cross:
            p["xattn"] = L.init_attention(ks[2], cfg)
            p["lnx"] = L.init_rmsnorm(d)
    elif kind == "rglru":
        p["rglru"] = RG.init_rglru(ks[0], cfg)
        p["ln2"] = L.init_rmsnorm(d)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind == "mlstm":
        p["mlstm"] = XL.init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["slstm"] = XL.init_slstm(ks[0], cfg)
    else:
        raise ValueError(kind)
    return p


def _residual(cfg: ModelConfig, h: jax.Array) -> jax.Array:
    """A residual branch's output as it is added to the stream: times
    ``cfg.residual_multiplier`` (Granite) where that is not 1, in float32
    and rounded once (0.22 has no exact bf16 value)."""
    m = cfg.residual_multiplier
    if m == 1.0:
        return h
    return (h.astype(jnp.float32) * m).astype(h.dtype)


def apply_block(p: Params, kind: str, cfg: ModelConfig, x: jax.Array, *,
                positions, cache=None, cache_index=None, memory=None,
                causal: bool = True, lossy: Optional[LossyCtx] = None,
                layer_key: Optional[jax.Array] = None,
                routes: bool = False, default_positions: bool = False):
    """Returns (x, new_cache, aux_loss, stats); ``stats`` holds an MoE
    block's counters (``moe.STATS``, and with ``routes`` its expert ids)
    and is empty for every other block.  ``default_positions``: the
    positions are 0..S-1 (``layers.attention``)."""
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    stats = {}

    if kind in ("global", "local", "moe"):
        h = L.seq_unpin(L.rmsnorm(p["ln1"], x, eps))
        a_cache = cache.get("attn") if cache else None
        h, new_attn_cache = L.attention(
            p["attn"], cfg, h, kind=("local" if kind == "local" else "global"),
            positions=positions, causal=causal,
            cache=a_cache, cache_index=cache_index,
            default_positions=default_positions)
        if cfg.post_norm:
            h = L.rmsnorm(p["pn1"], h, eps)
        x = x + _residual(cfg, h)

        if "xattn" in p and memory is not None:
            h = L.rmsnorm(p["lnx"], x, eps)
            h, _ = L.attention(p["xattn"], cfg, h, memory=memory,
                               positions=positions)
            x = x + _residual(cfg, h)

        h = L.seq_unpin(L.rmsnorm(p["ln2"], x, eps))
        if kind == "moe":
            h, aux, stats = MOE.moe_block(
                p["moe"], cfg, h,
                lossy=bool(lossy and lossy.enabled),
                key=(layer_key if lossy and lossy.enabled else None),
                drop_rate=(lossy.drop_rate if lossy else 0.0),
                routes=routes)
        else:
            h = L.mlp(p["mlp"], cfg, h)
        if cfg.post_norm:
            h = L.rmsnorm(p["pn2"], h, eps)
        x = x + _residual(cfg, h)
        new_cache = {"attn": new_attn_cache} if new_attn_cache else None
        return x, new_cache, aux, stats

    if kind == "rglru":
        h = L.seq_unpin(L.rmsnorm(p["ln1"], x, eps))
        h, new_rg = RG.rglru_block(p["rglru"], cfg, h,
                                   cache=cache.get("rglru") if cache else None)
        x = x + _residual(cfg, h)
        h = L.seq_unpin(L.rmsnorm(p["ln2"], x, eps))
        x = x + _residual(cfg, L.mlp(p["mlp"], cfg, h))
        return x, ({"rglru": new_rg} if new_rg else None), aux, stats

    if kind == "mlstm":
        h = L.rmsnorm(p["ln1"], x, eps)
        h, new_c = XL.mlstm_block(p["mlstm"], cfg, h,
                                  cache=cache.get("mlstm") if cache else None)
        return (x + _residual(cfg, h), ({"mlstm": new_c} if new_c else None),
                aux, stats)

    if kind == "slstm":
        h = L.rmsnorm(p["ln1"], x, eps)
        h, new_c = XL.slstm_block(p["slstm"], cfg, h,
                                  cache=cache.get("slstm") if cache else None)
        return (x + _residual(cfg, h), ({"slstm": new_c} if new_c else None),
                aux, stats)

    raise ValueError(kind)


# ----------------------------------------------------------------------
# Stacks (scan over pattern groups)
# ----------------------------------------------------------------------

def _init_stack(key: jax.Array, cfg: ModelConfig, n_layers: int,
                cross: bool = False) -> Params:
    plen = len(cfg.block_pattern)
    n_groups, tail = n_layers // plen, cfg.block_pattern[: n_layers % plen]
    stacked = []
    for j, kind in enumerate(cfg.block_pattern):
        keys = jax.random.split(jax.random.fold_in(key, j), max(n_groups, 1))
        init_one = functools.partial(init_block, kind=kind, cfg=cfg,
                                     cross=cross)
        stacked.append(jax.vmap(init_one)(keys) if n_groups else None)
    tailp = [init_block(jax.random.fold_in(key, 1000 + i), kind, cfg, cross)
             for i, kind in enumerate(tail)]
    return {"groups": stacked, "tail": tailp}


def _apply_stack(stack: Params, cfg: ModelConfig, n_layers: int,
                 x: jax.Array, *,
                 positions, caches=None, cache_index=None, memory=None,
                 causal: bool = True, lossy: Optional[LossyCtx] = None,
                 base_key: Optional[jax.Array] = None, remat: bool = True,
                 routes: bool = False, default_positions: bool = False):
    """caches: {"groups": [stacked per position], "tail": [per layer]}.
    Leading dense layers (``stack["lead"]``) run first, with no cache;
    ``n_layers`` counts the others.

    Returns (x, new_caches, aux, stats): the blocks' counters merged
    over layers (``moe.merge_stats``); ``stats`` also holds the
    per-layer ones (``moe.PER_LAYER``) stacked over the MoE layers in
    order: with ``routes`` ``moe_routes``, every MoE layer's expert ids
    (L, G, k), and with a router bias ``moe_counts`` (L, E)."""
    plen = len(cfg.block_pattern)
    n_groups = n_layers // plen
    tail_kinds = cfg.block_pattern[: n_layers % plen]
    aux_total = jnp.zeros((), jnp.float32)
    base_key = base_key if base_key is not None else jax.random.PRNGKey(0)

    # Sequence parallelism: pin the residual stream seq-sharded at unit
    # boundaries.  The remat/scan-carried activations then live at 1/TP
    # per device; attention/MLP internals reshard (all-to-all to heads,
    # reduce-scatter back) per Megatron-SP, emitted by GSPMD from the
    # constraints.  SP is a *training* trade (it shrinks remat storage);
    # forward-only serving pays its gathers for nothing [perf-iteration
    # H1: gemma2 prefill collective term dropped ~30x], so it is gated
    # on ``remat``.
    from repro import sharding as shd
    mesh = shd.get_global_mesh()
    seq_pin = None
    if (remat and mesh is not None and shd.MODEL_AXIS in mesh.shape
            and x.shape[1] > 1
            and x.shape[1] % mesh.shape[shd.MODEL_AXIS] == 0):
        nsp = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, shd.MODEL_AXIS, None))
        seq_pin = lambda t: jax.lax.with_sharding_constraint(t, nsp)

    if "lead" in stack and caches is not None:
        raise ValueError("leading dense layers take no KV cache here")
    for lead_p in stack.get("lead", ()):
        def dense(p_, x_):
            return apply_block(p_, "global", cfg, x_, positions=positions,
                               memory=memory, causal=causal,
                               default_positions=default_positions)
        if remat:
            dense = jax.checkpoint(dense)
        x = dense(lead_p, x)[0]

    def unit(x, slices, caches_slice, idx):
        new_caches, aux, stats = [], jnp.zeros((), jnp.float32), {}
        ids = {k: [] for k in MOE.PER_LAYER}
        if seq_pin is not None:
            x = seq_pin(x)
        for j, kind in enumerate(cfg.block_pattern):
            c = caches_slice[j] if caches_slice is not None else None
            lk = jax.random.fold_in(base_key, idx * plen + j)
            x, nc, a, st = apply_block(
                slices[j], kind, cfg, x, positions=positions, cache=c,
                cache_index=cache_index, memory=memory, causal=causal,
                lossy=lossy, layer_key=lk, routes=routes,
                default_positions=default_positions)
            new_caches.append(nc)
            aux = aux + a
            for k in MOE.PER_LAYER:
                if k in st:
                    ids[k].append(st.pop(k))
            stats = MOE.merge_stats(stats, st)
        if seq_pin is not None:
            x = seq_pin(x)
        return x, new_caches, aux, stats, ids

    stats, ids = {}, {k: [] for k in MOE.PER_LAYER}
    if n_groups:
        unit_fn = jax.checkpoint(unit) if remat else unit

        def body(carry, inp):
            x, aux, st = carry
            slices, cache_slice, idx = inp
            x, ncs, a, s_, i_ = unit_fn(x, slices, cache_slice, idx)
            return (x, aux + a, MOE.merge_stats(st, s_)), (ncs, i_)

        group_caches = caches["groups"] if caches is not None else None
        xs = (stack["groups"], group_caches, jnp.arange(n_groups))
        (x, aux_total, stats), (new_group_caches, g_ids) = jax.lax.scan(
            body, (x, aux_total, MOE.zero_stats(cfg)), xs)
        for k, per in g_ids.items():
            if per:   # (groups, MoE positions, ...) -> layers in order
                ids[k] = [jnp.stack(per, 1).reshape((-1,) + per[0].shape[1:])]
    else:
        new_group_caches = None

    new_tail = []
    for i, kind in enumerate(tail_kinds):
        c = caches["tail"][i] if caches is not None else None
        lk = jax.random.fold_in(base_key, n_groups * plen + i)
        def blk(p_, x_, *, _kind=kind, _c=c, _lk=lk):
            return apply_block(p_, _kind, cfg, x_, positions=positions,
                               cache=_c, cache_index=cache_index,
                               memory=memory, causal=causal, lossy=lossy,
                               layer_key=_lk, routes=routes,
                               default_positions=default_positions)
        if remat:
            blk = jax.checkpoint(blk)
        x, nc, a, st = blk(stack["tail"][i], x)
        new_tail.append(nc)
        aux_total = aux_total + a
        for k in MOE.PER_LAYER:
            if k in st:
                ids[k].append(st.pop(k)[None])
        stats = MOE.merge_stats(stats, st)

    for k, per in ids.items():
        if per:
            stats[k] = jnp.concatenate(per, 0)
    new_caches = None
    if caches is not None:
        new_caches = {"groups": new_group_caches, "tail": new_tail}
    return x, new_caches, aux_total, stats


# ----------------------------------------------------------------------
# Full model
# ----------------------------------------------------------------------

def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {
        "embed": L.init_embedding(ks[0], cfg),
        "decoder": _init_stack(ks[1], cfg, cfg.n_layers - cfg.n_dense_layers,
                               cross=cfg.is_encdec),
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    if cfg.n_dense_layers:   # leading dense layers, before the pattern
        p["decoder"]["lead"] = [
            init_block(jax.random.fold_in(ks[1], 2000 + i), "global", cfg)
            for i in range(cfg.n_dense_layers)]
    if cfg.is_encdec:
        p["encoder"] = _init_stack(ks[2], cfg, cfg.encoder_layers)
        p["enc_norm"] = L.init_rmsnorm(cfg.d_model)
    if cfg.frontend:
        dt = jnp.dtype(cfg.dtype)
        p["frontend_proj"] = (
            jax.random.truncated_normal(ks[3], -2., 2.,
                                        (cfg.frontend_dim, cfg.d_model),
                                        jnp.float32)
            * cfg.frontend_dim ** -0.5).astype(dt)
    return p


def _encode(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    """Audio/enc-dec encoder: frame embeddings -> memory (B,S_enc,D)."""
    frames = batch["frame_embeds"]                      # (B, S_enc, F)
    x = frames.astype(jnp.dtype(cfg.dtype)) @ params["frontend_proj"]
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
    x, _, _, _ = _apply_stack(params["encoder"], cfg, cfg.encoder_layers, x,
                              positions=pos, causal=False)
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            caches=None, cache_index=None, memory=None,
            lossy: Optional[LossyCtx] = None, remat: bool = True,
            positions: Optional[jax.Array] = None, last_only: bool = False,
            routes: bool = False):
    """Returns (logits, new_caches, aux_loss, stats): ``stats`` are the
    MoE counters (``moe.STATS``; empty without MoE layers), with
    ``routes`` also every MoE layer's expert ids (``moe_routes``).

    batch keys: "tokens" (B,S) always; "image_embeds" (vlm);
    "frame_embeds" (audio, encoder side — triggers encoder unless
    ``memory`` is already given).
    """
    tokens = batch["tokens"]
    x = L.embed(params["embed"], cfg, tokens)

    if cfg.frontend == "vision_stub" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(x.dtype) @ params["frontend_proj"]
        x = jnp.concatenate([img, x], axis=1)

    if cfg.is_encdec and memory is None and "frame_embeds" in batch:
        memory = _encode(params, cfg, batch)

    b, s = x.shape[:2]
    default_positions = positions is None
    if default_positions:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]

    x, new_caches, aux, stats = _apply_stack(
        params["decoder"], cfg, cfg.n_layers - cfg.n_dense_layers, x,
        positions=positions,
        caches=caches, cache_index=cache_index, memory=memory, lossy=lossy,
        remat=remat, routes=routes, default_positions=default_positions)

    if last_only:   # prefill: only the last position's logits are used
        x = x[:, -1:]
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, x)
    return logits, new_caches, aux, stats


def lm_loss(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            lossy: Optional[LossyCtx] = None, remat: bool = True):
    """Next-token cross-entropy (+ MoE aux), with the MoE counters:
    (loss, (nll, aux, stats)).  Loss only on text tokens."""
    logits, _, aux, stats = forward(params, cfg, batch, lossy=lossy,
                                    remat=remat)
    labels = batch["labels"]
    n_txt = labels.shape[1]
    logits = logits[:, -n_txt:][:, :-1]            # skip frontend positions
    tgt = labels[:, 1:]
    # Sharding-safe CE: every reduction runs over the (model-sharded)
    # vocab axis; no replicated f32 (B,S,V) tensor is ever materialized.
    mx = jax.lax.stop_gradient(
        jnp.max(logits, axis=-1, keepdims=True)).astype(jnp.float32)
    shifted = logits.astype(jnp.float32) - mx
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + mx[..., 0]
    onehot = jax.nn.one_hot(tgt, logits.shape[-1], dtype=logits.dtype)
    label_logit = jnp.sum(logits * onehot, axis=-1).astype(jnp.float32)
    nll = lse - label_logit
    return nll.mean() + aux, (nll.mean(), aux, stats)


# ----------------------------------------------------------------------
# Caches
# ----------------------------------------------------------------------

def _cache_len(kind: str, cfg: ModelConfig, s_max: int) -> int:
    if kind == "local":
        return min(cfg.window_size, s_max)
    return s_max


def init_layer_cache(kind: str, cfg: ModelConfig, batch: int, s_max: int):
    if cfg.mla is not None and kind in ATTN_KINDS:
        raise ValueError(f"{cfg.name}: latent attention has no KV cache "
                         "here (serving MLA needs a latent cache)")
    dt = jnp.dtype(cfg.dtype)
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if kind in ("global", "local", "moe", "xattn"):
        sc = _cache_len(kind, cfg, s_max)
        return {"attn": L.AttnCache(
            k=jnp.zeros((batch, sc, kv, hd), dt),
            v=jnp.zeros((batch, sc, kv, hd), dt),
            pos=jnp.full((sc,), -1, jnp.int32))}
    if kind == "rglru":
        return {"rglru": RG.init_cache(cfg, batch)}
    if kind == "mlstm":
        return {"mlstm": XL.init_mlstm_cache(cfg, batch)}
    if kind == "slstm":
        return {"slstm": XL.init_slstm_cache(cfg, batch)}
    raise ValueError(kind)


def init_caches(cfg: ModelConfig, batch: int, s_max: int):
    n_groups, tail = cfg.pattern_layout()
    groups = []
    for kind in cfg.block_pattern:
        one = init_layer_cache(kind, cfg, batch, s_max)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape), one) \
            if n_groups else None
        groups.append(stacked)
    tailc = [init_layer_cache(k, cfg, batch, s_max) for k in tail]
    return {"groups": groups, "tail": tailc}
