"""Serving: prefill + decode step factories.

- ``make_prefill``: (params, batch) -> (last-position logits, caches).
- ``make_decode``: (params, caches, tokens (B,1), index) -> (logits,
  caches) — one new token against a KV cache / recurrent state of
  ``s_max``; this is what the ``decode_32k`` / ``long_500k`` dry-run
  cells lower.

Sharding: batch over dp axes, params TP over 'model' (GSPMD).  KV-cache
heads are *not* forced onto the model axis (kv counts like 2 or 8 don't
divide 16); caches shard over batch, which is where decode parallelism
lives (the attention einsum for one token is bandwidth-bound on the
cache read, linear in B).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import coding
from repro.models import layers as L
from repro.models import model as M


def make_prefill(cfg: ModelConfig, s_max: int):
    def prefill(params, batch):
        tokens = batch["tokens"]
        b = tokens.shape[0]
        caches = M.init_caches(cfg, b, s_max)
        n_front = (cfg.n_frontend_tokens
                   if cfg.frontend == "vision_stub" else 0)
        positions = jnp.arange(tokens.shape[1] + n_front,
                               dtype=jnp.int32)[None, :]
        logits, caches, _, _ = M.forward(params, cfg, batch, caches=caches,
                                         positions=positions, remat=False,
                                         last_only=True)
        return logits[:, -1], caches
    return jax.jit(prefill)


def make_decode(cfg: ModelConfig):
    def decode(params, caches, batch, index):
        """index: scalar int32 — the position being generated."""
        positions = jnp.full((batch["tokens"].shape[0], 1), index,
                             dtype=jnp.int32)
        memory = batch.get("memory")       # enc-dec cross-attention
        logits, caches, _, _ = M.forward(
            params, cfg, {"tokens": batch["tokens"]}, caches=caches,
            cache_index=index, positions=positions, memory=memory,
            remat=False)
        return logits[:, -1], caches
    return jax.jit(decode, donate_argnums=(1,))


def greedy_decode(cfg: ModelConfig, params, caches, first_token: jax.Array,
                  start_idx: int, n_steps: int):
    """Greedy host-loop decode from an existing (possibly degraded) KV
    cache: ``first_token`` (B, 1) seeds the loop, ``start_idx`` is the
    cache position of the first generated token.  Returns
    (B, n_steps) tokens including ``first_token``."""
    decode = make_decode(cfg)
    out = [first_token]
    idx = start_idx
    for _ in range(n_steps - 1):
        logits, caches = decode(params, caches,
                                {"tokens": out[-1]}, jnp.int32(idx))
        out.append(jnp.argmax(logits, -1)[:, None])
        idx += 1
    return jnp.concatenate(out, axis=1)


def greedy_generate(cfg: ModelConfig, params, prompt: jax.Array,
                    n_steps: int, s_max: Optional[int] = None,
                    extra: Optional[Dict[str, Any]] = None):
    """Small host-loop generator for examples/tests (greedy)."""
    s_max = s_max or (prompt.shape[1] + n_steps)
    batch = {"tokens": prompt, **(extra or {})}
    prefill = make_prefill(cfg, s_max)
    logits, caches = prefill(params, batch)
    first = jnp.argmax(logits, -1)[:, None]
    return greedy_decode(cfg, params, caches, first, prompt.shape[1], n_steps)


# ----------------------------------------------------------------------
# Degraded-KV decode: ship caches through the lossy transport's wire
# layout (serve/traffic.py -> coupling.kv_hole_masks -> here)
# ----------------------------------------------------------------------

def kv_wire_roundtrip(flat: jax.Array, mask: jax.Array, signs: jax.Array,
                      plan: coding.NdPlan, *, coded: bool = True
                      ) -> jax.Array:
    """One flat KV payload through the wire: encode (or just block),
    drop the wire rows where ``mask`` is 0, decode.

    ``mask`` (n_rot,) is one request's transport-block arrival mask
    (``coupling.kv_hole_masks`` row) — the payload ships as ``n_rot``
    transport blocks either way, and the same block indices are lost
    either way; the two layouts differ in what a block *carries*:

    - ``coded=True``: block ``j`` is wire row ``j`` of the Hadamard
      layout — coordinate ``j`` of every rotation block
      (``coding.encode_nd``).  Lost rows are unbiased over by
      ``coding.decode_nd``, so the damage is small dense noise spread
      across the entire payload.
    - ``coded=False``: block ``j`` is the ``j``-th *contiguous chunk*
      of the raw payload (how an uncoded sender packs KV).  Lost
      chunks are holes: whole spans of cache positions zeroed —
      exactly the trainer's plain-lossy ablation, applied to serving.
    """
    mask = mask.astype(flat.dtype)
    if coded:
        tiles = coding.encode_nd(flat, signs, plan) * mask[None, :, None]
        return coding.decode_nd(tiles, mask, signs, plan, total_peers=1)
    x = jnp.pad(flat, (0, plan.tiles * plan.n_rot - plan.m_orig))
    chunks = x.reshape(plan.n_rot, plan.tiles) * mask[:, None]
    return chunks.reshape(-1)[: plan.m_orig]


def degrade_caches(caches, mask: jax.Array, key: jax.Array, *,
                   coded: bool = True):
    """Apply one request's KV-transfer loss to its decode caches.

    Every attention layer's K and V tensors are flattened, shipped
    through :func:`kv_wire_roundtrip` under the same wire-row mask
    (all of a request's KV blocks ride the same cut rounds), and
    restored in place; recurrent state and cache positions are
    metadata the transport does not code, and pass through untouched.
    ``key`` seeds the shared rotation signs — prefill and decode sides
    must agree on it, exactly like the trainer's coded all-reduce.
    """
    def _ship(leaf):
        plan = coding.plan_nd((int(leaf.size),), None, int(mask.shape[0]))
        if plan.n_rot != int(mask.shape[0]):
            raise ValueError(
                f"KV leaf of {leaf.size} elements cannot carry a "
                f"{mask.shape[0]}-row wire mask (plan chose {plan.n_rot})")
        signs = coding.rademacher_nd(key, plan)
        out = kv_wire_roundtrip(leaf.reshape(-1).astype(jnp.float32),
                                mask, signs, plan, coded=coded)
        return out.reshape(leaf.shape).astype(leaf.dtype)

    def _one(node):
        if not isinstance(node, L.AttnCache):
            return node
        return dataclasses.replace(node, k=_ship(node.k), v=_ship(node.v))

    return jax.tree_util.tree_map(
        _one, caches, is_leaf=lambda x: isinstance(x, L.AttnCache))


def kv_position_error(clean, degraded, n_ctx: int):
    """(n_ctx,) per-position relative KV error after lossy transfer.

    For each cache position ``s < n_ctx`` (the prefilled context), the
    relative L2 error of its K/V vectors aggregated over every
    attention layer — the serving counterpart of the trainer's
    gradient-error metric.  An uncoded lost chunk drives whole
    positions to error ~1 (their context is simply gone at the decode
    node); the coded path spreads the same loss as uniform small noise
    across all positions.  ``usable fraction`` (positions under an
    error threshold) is fig8's recovery metric.
    """
    def _leaves(tree):
        nodes = jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, L.AttnCache))
        return [n for n in nodes if isinstance(n, L.AttnCache)]

    err2 = jnp.zeros(n_ctx)
    ref2 = jnp.zeros(n_ctx)
    for c0, c1 in zip(_leaves(clean), _leaves(degraded)):
        for a0, a1 in ((c0.k, c1.k), (c0.v, c1.v)):
            # (..., S, kv, hd): fold everything but the position axis
            s_ax = a0.ndim - 3
            d = jnp.moveaxis((a1 - a0) ** 2, s_ax, 0)
            r = jnp.moveaxis(a0.astype(jnp.float32) ** 2, s_ax, 0)
            err2 = err2 + d[:n_ctx].reshape(n_ctx, -1).sum(1)
            ref2 = ref2 + r[:n_ctx].reshape(n_ctx, -1).sum(1)
    return jnp.sqrt(err2 / jnp.maximum(ref2, 1e-12))
