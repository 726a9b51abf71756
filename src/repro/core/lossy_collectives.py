"""Lossy (best-effort) collectives — Celeris semantics on a TPU mesh.

TPU ICI is lossless, so Celeris's "packets that miss the bounded window
are discarded" is emulated at *wire-row granularity inside the
collective*: every participant samples a per-(peer, wire-row) arrival
mask from the step's drop probability (itself derived from the timeout
controller + transport latency model) and contributes only the rows that
"arrived".  Receivers finalize with what they have — exactly the
receiver-side semantics of the paper's §III-B — and recover through the
Hadamard coding layer (:mod:`repro.core.coding`).

:func:`lossy_psum` is the coded AllReduce of one gradient leaf, the one
the train step's dp-manual island runs per coded leaf.  It is
shard_map-compatible and lowers to plain ``psum`` / ``pmax`` HLOs plus
elementwise masking, so the dry-run (16x16 and 2x16x16 meshes) sees
ordinary TPU collectives.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro import sharding as shd
from repro.core import coding

AxisNames = str | Sequence[str]


def arrival_mask(key: jax.Array, n_rows: int, drop_rate: jax.Array) -> jax.Array:
    """Bernoulli(1 - drop_rate) per wire row: True = arrived in window."""
    return jax.random.uniform(key, (n_rows,)) >= drop_rate


def _psum(x, axes):
    with jax.named_scope("psum"):
        return jax.lax.psum(x, axes)


def quantize_rows(x: jax.Array, scale: jax.Array, noise: jax.Array
                  ) -> jax.Array:
    """Stochastic int8-range codes of (tiles, n_rot, Ns) wire tiles on
    one grid per wire row: ``clip(floor(x / scale + noise), -127, 127)``
    with ``scale`` (n_rot,) the row's absmax / 127 and ``noise`` uniform
    on [0, 1), held in int16 so that a psum over up to 258 peers cannot
    overflow."""
    return jnp.clip(jnp.floor(x / scale[None, :, None] + noise),
                    -127, 127).astype(jnp.int16)


def lossy_psum(g: jax.Array, axis_name: AxisNames, *, plan: coding.NdPlan,
               signs: jax.Array, key: jax.Array, leaf: int,
               peer_id: jax.Array, drop_rate: jax.Array,
               quantize_wire: bool = False, wire_dtype: str = "float32"
               ) -> tuple[jax.Array, jax.Array]:
    """Best-effort AllReduce of gradient leaf number ``leaf``, ``g``,
    over ``axis_name``.

    Each peer encodes ``g`` (:func:`coding.encode_nd` under ``signs``
    (n_rot,), the same on every peer), keeps the wire rows that its
    arrival mask lets through, and the peers psum their tiles and their
    masks; the decode unbiases by the arrivals.  The mask is drawn from
    ``fold_in(fold_in(key, 2 * leaf + 1), peer_id)`` at ``drop_rate``;
    ``peer_id`` is this shard's index along ``axis_name``, passed in as
    data because ``axis_index`` does not lower under partial-auto
    shard_map.  Returns ``(estimate of the sum of g over the peers, f32
    in g's shape; arrivals per wire row (n_rot,))``.

    ``quantize_wire``: every peer's kept tiles share one scale per wire
    row (a ``pmax`` of their absmax, n_rot scalars), are rounded by
    :func:`quantize_rows` with noise from ``fold_in(key, 3 * leaf + 2)``
    and summed in int16, half the collective bytes of f32.  Otherwise
    the tiles travel in ``wire_dtype``.
    """
    peers = shd.axis_size(axis_name)
    with jax.named_scope("encode"):
        tiles = coding.encode_nd(g, signs, plan)
    with jax.named_scope("mask"):
        mask = arrival_mask(
            jax.random.fold_in(jax.random.fold_in(key, 2 * leaf + 1),
                               peer_id), plan.n_rot, drop_rate)
        contrib = tiles * mask[None, :, None].astype(tiles.dtype)
    if quantize_wire:
        with jax.named_scope("psum"):
            absmax = jax.lax.pmax(jnp.max(jnp.abs(contrib), axis=(0, 2)),
                                  axis_name)
        with jax.named_scope("encode"):
            scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
            noise = jax.random.uniform(
                jax.random.fold_in(key, 3 * leaf + 2), contrib.shape)
            q = quantize_rows(contrib, scale, noise)
        tiles_sum = (_psum(q, axis_name).astype(jnp.float32)
                     * scale[None, :, None])
    else:
        contrib = contrib.astype(jnp.dtype(wire_dtype))
        tiles_sum = _psum(contrib, axis_name).astype(jnp.float32)
    counts = _psum(mask.astype(jnp.float32), axis_name)
    with jax.named_scope("decode"):
        est = coding.decode_nd(tiles_sum, counts, signs, plan,
                               total_peers=peers)
    return est, counts
