"""Foundational model layers (pure-functional JAX).

Covers every attention/MLP variant the assigned architectures need:
GQA with arbitrary kv-head counts, QKV bias, attention/logit softcaps
(gemma2), local sliding windows, partial RoPE (chatglm's 2d rope =
rotary on half the head dim), squared-ReLU / SwiGLU / GeGLU MLPs.

Parameters are plain pytrees; ``init_*`` builds them, ``apply_*`` runs
them.  Everything is shape-polymorphic over (batch, seq).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------

def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + p["scale"])).astype(dt)


# ----------------------------------------------------------------------
# RoPE (standard + partial fraction for chatglm 2d rope)
# ----------------------------------------------------------------------

def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                fraction: float) -> tuple[jax.Array, jax.Array, int]:
    """cos/sin tables over the rotary sub-dimension.

    positions: (..., S) int32.  Returns (cos, sin, rot_dim) where
    rot_dim = head_dim * fraction (rounded to even).
    """
    rot_dim = int(head_dim * fraction) // 2 * 2
    freq = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                            / rot_dim))
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, rot/2)
    return jnp.cos(angles), jnp.sin(angles), rot_dim


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               rot_dim: int) -> jax.Array:
    """x: (B, S, H, Dh); rotates the first rot_dim dims, pass-through rest."""
    rot, rest = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = rot[..., 0::2], rot[..., 1::2]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    y1 = x1 * c - x2 * s
    y2 = x1 * s + x2 * c
    y = jnp.stack([y1, y2], axis=-1).reshape(rot.shape)
    return jnp.concatenate([y, rest], axis=-1) if rest.shape[-1] else y


# ----------------------------------------------------------------------
# Attention (GQA / local / softcap / bias / cache)
# ----------------------------------------------------------------------

def _trunc_normal(key, shape, scale, dtype):
    fan_in = shape[0] if len(shape) >= 1 else 1
    std = scale / (fan_in ** 0.5)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def init_attention(key: jax.Array, cfg: ModelConfig) -> Params:
    if cfg.mla is not None:
        return init_mla(key, cfg)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, hp, kv = cfg.n_heads, cfg.n_heads_padded, cfg.n_kv_heads
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    wq = _trunc_normal(ks[0], (d, hp * hd), 1.0, dt)
    wo = _trunc_normal(ks[3], (hp * hd, d), 1.0, dt)
    if hp != h:   # inert TP-padding heads: zeroed in and out at init
        mask = (jnp.arange(hp * hd) < h * hd).astype(dt)
        wq = wq * mask[None, :]
        wo = wo * mask[:, None]
    p: Params = {
        "wq": wq,
        "wk": _trunc_normal(ks[1], (d, kv * hd), 1.0, dt),
        "wv": _trunc_normal(ks[2], (d, kv * hd), 1.0, dt),
        "wo": wo,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hp * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    return p


def init_mla(key: jax.Array, cfg: ModelConfig) -> Params:
    """Latent attention's weights: ``wq`` (d, H x qk_head_dim), ``wkv_a``
    (d, kv_lora_rank + qk_rope_head_dim) to the latent and the shared
    rope key, the latent's norm, ``wkv_b`` (kv_lora_rank, H x (nope +
    v)) to each head's key and value, ``wo`` (H x v_head_dim, d)."""
    d, h, a = cfg.d_model, cfg.n_heads, cfg.mla
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    return {
        "wq": _trunc_normal(ks[0], (d, h * a.qk_head_dim), 1.0, dt),
        "wkv_a": _trunc_normal(
            ks[1], (d, a.kv_lora_rank + a.qk_rope_head_dim), 1.0, dt),
        "kv_norm": init_rmsnorm(a.kv_lora_rank),
        "wkv_b": _trunc_normal(
            ks[2], (a.kv_lora_rank, h * (a.qk_nope_head_dim + a.v_head_dim)),
            1.0, dt),
        "wo": _trunc_normal(ks[3], (h * a.v_head_dim, d), 1.0, dt),
    }


def _head_shard(x: jax.Array) -> jax.Array:
    """Constrain (B,S,H,Dh) onto the model axis over heads when legal."""
    from repro import sharding as shd
    mesh = shd.get_global_mesh()
    if mesh is None or shd.MODEL_AXIS not in mesh.shape:
        return x
    tp = mesh.shape[shd.MODEL_AXIS]
    if x.ndim != 4 or x.shape[2] % tp:
        return x
    U = jax.sharding.PartitionSpec.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(U, None, shd.MODEL_AXIS,
                                             None)))


def _proj_shard(t: jax.Array, n_heads: int) -> jax.Array:
    """Pin a (B,S,n*Dh) projection BEFORE the head reshape.

    The old SPMD partitioner cannot reshard seq-sharded -> head-sharded
    through a reshape (it falls back to full rematerialization, and for
    tiny kv-head counts even hits a partitioner CHECK crash).  Pinning
    the merged dim here makes the later reshape a clean H-major split:
    - heads % tp == 0 (always true for padded q heads): shard last dim;
    - small kv: force replicated over model (GQA kv tensors are tiny —
      that is the entire point of GQA).
    Batch stays UNCONSTRAINED so serving jits keep dp batch sharding.
    """
    from repro import sharding as shd
    mesh = shd.get_global_mesh()
    if (mesh is None or t.ndim != 3
            or shd.MODEL_AXIS not in mesh.shape):
        return t
    tp = mesh.shape[shd.MODEL_AXIS]
    last = shd.MODEL_AXIS if (n_heads % tp == 0
                              and t.shape[-1] % tp == 0) else None
    U = jax.sharding.PartitionSpec.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(
        t, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(U, None, last)))


def seq_unpin(x: jax.Array) -> jax.Array:
    """Identity.  [Perf-iteration H2, REFUTED: forcing one full-sequence
    materialization per sub-block did not deduplicate the per-projection
    gathers (GSPMD already shares them), and its backward transpose
    added a (B,S,D) f32 grad all-reduce per use: nemotron train AR bytes
    +96 GiB, collective term 13.5s -> 15.3s.  Kept as a hook; the
    constraint itself was removed.]"""
    return x


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "pos"), meta_fields=())
@dataclasses.dataclass
class AttnCache:
    """Decode-time KV cache for one attention layer.

    Local-attention layers use a ring cache of window size: slot =
    position % S_cache; ``pos`` tracks each slot's true position (-1 =
    empty) so masking and RoPE stay exact after wraparound.
    """
    k: jax.Array      # (B, S_cache, KV, Dh)
    v: jax.Array
    pos: jax.Array    # (S_cache,) int32, -1 when empty


def _cache_prefill(cache: "AttnCache", k, v) -> "AttnCache":
    """Write a length-L prefix into the (possibly smaller ring) cache."""
    b, l = k.shape[:2]
    sc = cache.k.shape[1]
    if l <= sc:
        ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                          (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                          (0, 0, 0, 0))
        pos = cache.pos.at[:l].set(jnp.arange(l, dtype=jnp.int32))
        return AttnCache(k=ck, v=cv, pos=pos)
    # ring: keep the last sc tokens; slot(i) = i % sc
    kt, vt = k[:, -sc:], v[:, -sc:]
    start = (l - sc) % sc          # slot of the oldest kept token
    split = sc - start
    ck, cv, pos = cache.k, cache.v, cache.pos
    ck = jax.lax.dynamic_update_slice(ck, kt[:, :split].astype(ck.dtype),
                                      (0, start, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, vt[:, :split].astype(cv.dtype),
                                      (0, start, 0, 0))
    pos = jax.lax.dynamic_update_slice(
        pos, jnp.arange(l - sc, l - sc + split, dtype=jnp.int32), (start,))
    if start:
        ck = jax.lax.dynamic_update_slice(ck, kt[:, split:].astype(ck.dtype),
                                          (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, vt[:, split:].astype(cv.dtype),
                                          (0, 0, 0, 0))
        pos = jax.lax.dynamic_update_slice(
            pos, jnp.arange(l - start, l, dtype=jnp.int32), (0,))
    return AttnCache(k=ck, v=cv, pos=pos)


def _cache_decode(cache: "AttnCache", k, v, index) -> "AttnCache":
    """Insert one token at true position ``index`` (ring slot = mod)."""
    sc = cache.k.shape[1]
    slot = jax.lax.rem(index.astype(jnp.int32), jnp.int32(sc))
    ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                      (0, slot, 0, 0))
    pos = jax.lax.dynamic_update_slice(
        cache.pos, index.astype(jnp.int32)[None], (slot,))
    return AttnCache(k=ck, v=cv, pos=pos)


FLASH_THRESHOLD = 4 * 1024 * 1024   # s_q * s_kv from which we tile


def attention_scale(cfg: ModelConfig, head_dim: int) -> float:
    """What attention scores are multiplied by: the configuration's
    ``attention_multiplier`` (Granite: 1/head_dim), else 1/sqrt(head_dim)."""
    if cfg.attention_multiplier is not None:
        return cfg.attention_multiplier
    return head_dim ** -0.5


def attention_path(cfg: ModelConfig, s: int, s_kv: int, *, decode: bool,
                   cross: bool, default_positions: bool,
                   kind: str = "global", causal: bool = True) -> str:
    """How ``attention`` computes s queries over s_kv keys: ``"pallas"``
    (``ops.flash_attention``), ``"tiled"`` (``_flash_attention`` in
    jnp) or ``"dense"`` (the whole score matrix).

    Long attention (``s * s_kv >= FLASH_THRESHOLD``, not decode) is
    tiled.  It takes the Pallas kernel where the kernel computes the
    same thing: no global mesh (Mosaic kernels cannot be partitioned
    automatically), causal global self-attention without softcap, at
    the default positions 0..s-1 and a length the kernel's tiles divide.
    """
    from repro import sharding as shd
    if decode or s * s_kv < FLASH_THRESHOLD:
        return "dense"
    if (shd.get_global_mesh() is None and not cross and causal
            and kind == "global" and cfg.attn_softcap is None
            and default_positions and ops.flash_attention_fits(s)):
        return "pallas"
    return "tiled"


def _flash_attention(q, k, v, *, qpos, kpos, kind: str, cfg: ModelConfig,
                     causal: bool, q_blk: int = 1024, kv_blk: int = 1024):
    """Memory-efficient attention (Rabe–Staats style, mask-aware).

    q/k: (B,Sq|Skv,H,D), v: (B,Skv,H,Dv); qpos (Sq,), kpos (Skv,) true
    positions.  Online softmax over kv tiles inside a scan over q tiles;
    each q-tile is jax.checkpoint'ed so backward recomputes tiles instead
    of storing O(Sq*Skv) residuals.  Never materializes (Sq, Skv).
    """
    b, sq, h, d = q.shape
    skv, dv = k.shape[1], v.shape[-1]
    q_blk = min(q_blk, sq)
    kv_blk = min(kv_blk, skv)
    assert sq % q_blk == 0 and skv % kv_blk == 0, (sq, q_blk, skv, kv_blk)
    nq, nk = sq // q_blk, skv // kv_blk
    scale = attention_scale(cfg, d)

    qr = q.reshape(b, nq, q_blk, h, d).swapaxes(0, 1)     # (nq,B,qb,H,D)
    kr = k.reshape(b, nk, kv_blk, h, d).swapaxes(0, 1)
    vr = v.reshape(b, nk, kv_blk, h, dv).swapaxes(0, 1)
    qpr = qpos.reshape(nq, q_blk)
    kpr = kpos.reshape(nk, kv_blk)

    def q_tile(qt, qp):
        """qt: (B,qb,H,D); returns (B,qb,H,Dv)."""
        def kv_step(carry, t):
            m, l, acc = carry
            kt, vt, kp = t
            # bf16 operands + f32 accumulation (preferred_element_type):
            # keeps backward cotangents in bf16 — [perf-iteration H5:
            # nemotron train f32 activation AG/AR bytes halved]
            s = jnp.einsum("bqhd,bkhd->bhqk", qt, kt,
                           preferred_element_type=jnp.float32) * scale
            if cfg.attn_softcap is not None:
                s = _softcap(s, cfg.attn_softcap)
            mask = kp[None, None, None, :] >= 0
            if causal:
                mask = mask & (kp[None, None, None, :]
                               <= qp[None, None, :, None])
            if kind == "local":
                mask = mask & (kp[None, None, None, :]
                               > qp[None, None, :, None] - cfg.window_size)
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p_ = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p_.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p_.astype(vt.dtype), vt,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_blk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, q_blk), jnp.float32)
        a0 = jnp.zeros((b, h, q_blk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kr, vr, kpr))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.swapaxes(1, 2)                        # (B,qb,H,Dv)

    outs = jax.lax.scan(
        lambda _, t: (None, jax.checkpoint(q_tile)(t[0], t[1])),
        None, (qr, qpr))[1]                              # (nq,B,qb,H,Dv)
    return outs.swapaxes(0, 1).reshape(b, sq, h, dv)


def _core(cfg: ModelConfig, q, kq, vq, *, path: str, scale: float,
          x_dtype, positions, kind: str, causal: bool, cross: bool,
          decode: bool, kpos1, cache_index):
    """Attention of (B,S,H,D) queries over (B,S_kv,KV,D) keys and
    (B,S_kv,KV,Dv) values by ``path`` (``attention_path``): (B,S,H,Dv)."""
    b, s, h, hd = q.shape
    kv, s_kv = kq.shape[2], kq.shape[1]
    rep = h // kv
    if path == "pallas":
        # rope left q and k in float32: the kernel takes them in the
        # compute dtype, as the MXU does the dense path's matmuls
        out = ops.flash_attention(q.astype(x_dtype), kq.astype(x_dtype),
                                  vq, scale=scale)
    elif path == "tiled":
        # memory-efficient path: never builds the (Sq,Skv) matrix;
        # cross-attention uses it too (causal=False, all-valid kpos)
        kq = _head_shard(jnp.repeat(kq, rep, axis=2))
        vq = _head_shard(jnp.repeat(vq, rep, axis=2))
        qpos1 = positions[0] if positions.ndim == 2 else positions
        kpos_arr = jnp.arange(s_kv, dtype=jnp.int32)
        out = _flash_attention(
            q, kq, vq, qpos=qpos1, kpos=kpos_arr,
            kind=("global" if cross else kind), cfg=cfg,
            causal=(causal and not cross)).astype(x_dtype)
    else:
        # dense path: grouped-GQA einsums against the UNREPEATED kv (a
        # materialized repeat of a 32k-token cache would cost GBs at
        # decode)
        qg = q.reshape(b, s, kv, rep, hd)
        logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, kq,
                            preferred_element_type=jnp.float32) * scale
        logits = logits.reshape(b, h, s, s_kv)
        logits = _softcap(logits, cfg.attn_softcap)

        # masks
        if not cross:
            if decode:
                kpos = kpos1[None, None, None, :]   # true slot positions
                mask = (kpos >= 0) & (kpos <= cache_index)
                if kind == "local":
                    mask = mask & (kpos > cache_index - cfg.window_size)
            else:
                qpos = positions[:, None, :, None]
                kpos = jnp.arange(s_kv)[None, None, None, :]
                mask = (kpos <= qpos) if causal else jnp.ones(
                    (1, 1, s, s_kv), bool)
                if kind == "local":
                    mask = mask & (kpos > qpos - cfg.window_size)
            logits = jnp.where(mask, logits, -1e30)

        attn = jax.nn.softmax(logits, axis=-1).astype(vq.dtype)
        attn_g = attn.reshape(b, kv, rep, s, s_kv)
        out = jnp.einsum("bkrqs,bskd->bqkrd", attn_g, vq)
    return out


def attention(p: Params, cfg: ModelConfig, x: jax.Array, *,
              kind: str = "global",
              positions: Optional[jax.Array] = None,
              causal: bool = True,
              cache: Optional[AttnCache] = None,
              cache_index: Optional[jax.Array] = None,
              memory: Optional[jax.Array] = None,
              default_positions: bool = False,
              ) -> tuple[jax.Array, Optional[AttnCache]]:
    """GQA attention.

    Modes:
    - train/prefill: full (B,S,D) in, optional returned cache.
    - decode: S==1 with ``cache``+``cache_index`` (static-shape update).
    - cross-attention: ``memory`` (B,S_enc,D) supplies K/V, no cache/rope.

    ``positions`` None, or ``default_positions``, says they are 0..S-1:
    only then may long attention take the Pallas kernel
    (``attention_path``).  A configuration with ``mla`` runs
    :func:`mla_attention` instead (self-attention, no cache).
    """
    if cfg.mla is not None:
        if cache is not None or memory is not None:
            raise ValueError("latent attention runs without a KV cache and "
                             "without cross-attention: serving MLA needs a "
                             "latent cache, which this program lacks")
        return mla_attention(p, cfg, x, kind=kind, positions=positions,
                             causal=causal,
                             default_positions=default_positions), None
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads_padded, cfg.n_kv_heads, cfg.resolved_head_dim

    q = x @ p["wq"]
    kv_src = memory if memory is not None else x
    k = kv_src @ p["wk"]
    v = kv_src @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _proj_shard(q, h)
    k = _proj_shard(k, kv)
    v = _proj_shard(v, kv)
    q = _head_shard(q.reshape(b, s, h, hd))
    k = k.reshape(b, kv_src.shape[1], kv, hd)
    v = v.reshape(b, kv_src.shape[1], kv, hd)

    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
        default_positions = True
    if memory is None:   # self-attention: rope
        cos, sin, rot = rope_tables(positions, hd, cfg.rope_theta,
                                    cfg.rope_fraction)
        q = apply_rope(q, cos, sin, rot)
        k = apply_rope(k, cos, sin, rot)

    new_cache = None
    if cache is not None:
        if s == 1 and cache_index is not None:     # decode: insert at index
            new_cache = _cache_decode(cache, k, v, cache_index)
        else:                                       # prefill
            new_cache = _cache_prefill(cache, k, v)

    decode = new_cache is not None and s == 1
    if decode:
        kq, vq, kpos1 = new_cache.k, new_cache.v, new_cache.pos
    else:
        kq, vq = k, v
        kpos1 = None
    s_kv = kq.shape[1]

    path = attention_path(cfg, s, s_kv, decode=decode,
                          cross=memory is not None,
                          default_positions=default_positions, kind=kind,
                          causal=causal)
    scale = attention_scale(cfg, hd)

    with jax.named_scope("attention"):
        out = _core(cfg, q, kq, vq, path=path, scale=scale, x_dtype=x.dtype,
                    positions=positions, kind=kind, causal=causal,
                    cross=memory is not None, decode=decode, kpos1=kpos1,
                    cache_index=cache_index)
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, new_cache


def mla_attention(p: Params, cfg: ModelConfig, x: jax.Array, *,
                  kind: str = "global",
                  positions: Optional[jax.Array] = None,
                  causal: bool = True,
                  default_positions: bool = False) -> jax.Array:
    """Multi-head latent attention (DeepSeek-V3 without a query latent)
    of (B,S,D) ``x``: each head's query is [q_nope, rope(q_rope)], its
    key [k_nope, rope(k_rope)] and its value v, with k_nope and v from
    the normed ``kv_lora_rank`` latent through ``wkv_b`` and one rope
    key shared by the heads; scores x ``attention_scale`` of the
    qk_head_dim.  Under ``attention``: the projections to q, k and v
    under ``latent``, the scores and values (``attention_path``'s path)
    under ``core``."""
    b, s, _ = x.shape
    a, h = cfg.mla, cfg.n_heads
    nope, rope = a.qk_nope_head_dim, a.qk_rope_head_dim
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
        default_positions = True
    with jax.named_scope("attention"):
        with jax.named_scope("latent"):
            q = (x @ p["wq"]).reshape(b, s, h, a.qk_head_dim)
            ckv = x @ p["wkv_a"]
            latent = rmsnorm(p["kv_norm"], ckv[..., :a.kv_lora_rank],
                             cfg.norm_eps)
            kv = (latent @ p["wkv_b"]).reshape(b, s, h, nope + a.v_head_dim)
            cos, sin, rot = rope_tables(positions, rope, cfg.rope_theta, 1.0)
            q_rope = apply_rope(q[..., nope:], cos, sin, rot)
            k_rope = apply_rope(ckv[..., None, a.kv_lora_rank:], cos, sin,
                                rot)
            q = jnp.concatenate([q[..., :nope], q_rope.astype(x.dtype)], -1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope.astype(x.dtype), (b, s, h, rope))],
                -1)
            v = kv[..., nope:]
        path = attention_path(cfg, s, s, decode=False, cross=False,
                              default_positions=default_positions,
                              kind=kind, causal=causal)
        with jax.named_scope("core"):
            out = _core(cfg, q, k, v, path=path,
                        scale=attention_scale(cfg, a.qk_head_dim),
                        x_dtype=x.dtype, positions=positions, kind=kind,
                        causal=causal, cross=False, decode=False,
                        kpos1=None, cache_index=None)
        return out.reshape(b, s, h * a.v_head_dim) @ p["wo"]


# ----------------------------------------------------------------------
# MLP variants
# ----------------------------------------------------------------------

def init_mlp(key: jax.Array, cfg: ModelConfig) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {"wi": _trunc_normal(ks[0], (d, f), 1.0, dt),
                "wg": _trunc_normal(ks[1], (d, f), 1.0, dt),
                "wo": _trunc_normal(ks[2], (f, d), 1.0, dt)}
    if cfg.mlp_type == "sqrelu":
        return {"wi": _trunc_normal(ks[0], (d, f), 1.0, dt),
                "wo": _trunc_normal(ks[2], (f, d), 1.0, dt)}
    raise ValueError(cfg.mlp_type)


def mlp(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.mlp_type == "swiglu":
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]
    if cfg.mlp_type == "geglu":
        return (jax.nn.gelu(x @ p["wg"], approximate=True) * (x @ p["wi"])) @ p["wo"]
    if cfg.mlp_type == "sqrelu":
        return jnp.square(jax.nn.relu(x @ p["wi"])) @ p["wo"]
    raise ValueError(cfg.mlp_type)


# ----------------------------------------------------------------------
# Embedding
# ----------------------------------------------------------------------

def init_embedding(key: jax.Array, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    p: Params = {"table": _trunc_normal(key, (cfg.vocab_size, cfg.d_model),
                                        1.0, dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = _trunc_normal(
            jax.random.fold_in(key, 1), (cfg.d_model, cfg.vocab_size), 1.0, dt)
    return p


def embed(p: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["table"], tokens, axis=0)
    m = cfg.embedding_multiplier
    return x * jnp.asarray(cfg.d_model ** 0.5 if m is None else m, x.dtype)


def unembed(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Returns logits in the compute dtype (bf16), vocab-sharded.

    Keeping (B,S,V) out of f32/replicated is what keeps the train step's
    temp memory sane at 256k vocabs — the loss does its reductions in
    f32 without materializing a full-precision logits tensor.  Logits
    are divided by ``cfg.logits_scaling`` (Granite) where it is not 1.
    """
    if cfg.tie_embeddings:
        logits = x @ p["table"].T
    else:
        logits = x @ p["unembed"]
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    if cfg.logit_softcap is not None:
        logits = _softcap(logits.astype(jnp.float32),
                          cfg.logit_softcap).astype(x.dtype)
    from repro import sharding as shd
    mesh = shd.get_global_mesh()
    if (mesh is not None and shd.MODEL_AXIS in mesh.shape
            and logits.ndim == 3
            and logits.shape[-1] % mesh.shape[shd.MODEL_AXIS] == 0):
        logits = jax.lax.with_sharding_constraint(
            logits, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(None, None, shd.MODEL_AXIS)))
    return logits
