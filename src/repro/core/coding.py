"""ML-pipeline loss recovery (paper §III-B, last paragraph).

Celeris ships no transport-layer recovery; instead the framework encodes
collective payloads so that *bounded, partial* loss is absorbed:

**Randomized Hadamard rotation** (a la OptiReduce / Fig. 1):
    encode:  y = (1/sqrt(n)) H D x     per rotation block of width n
    decode:  x_hat = (n/k) (1/sqrt(n)) D H S y   (S = arrival mask, k = |S|)
  which is exactly unbiased (E[x_hat] = x) and lossless when k = n.

**Wire interleaving** — rotation must span *more* than the loss
granularity or a dropped chunk would take a whole rotation block with
it.  A leaf is cut into rotation blocks laid out as ``(tiles, n_rot,
Ns)`` (:func:`to_tiles_nd`); the rotation runs along the middle axis,
and wire row ``j`` is coordinate ``j`` of *every* block (``t[:, j,
:]``).  A lost row removes a 1/n coordinate slice from each block and
the unbiased rescale recovers the rest: the paper's "critical
information ... split across packets for partial recovery".  Arrival
masks are ``(n_rot,)`` vectors over those rows.

This one coder (:class:`NdPlan`, :func:`encode_nd`, :func:`decode_nd`)
serves every path: the train step's one-device sync (as the Pallas
``coded_roundtrip`` kernel, :func:`roundtrip_nd`, where the tiles are
lane-wide flat rows), its dp-mesh psum
(``lossy_collectives.lossy_psum``) and the serve path's KV transfer.
Outside the kernel the transform is :func:`fwht_nd`, two ``HIGHEST``
contractions.

**XOR parity** — exact recovery of any single lost chunk per parity
group (the paper's lightweight coding alternative for prioritized data,
e.g. activation shards under lossy TP).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


# ----------------------------------------------------------------------
# Sharding-aware ND coding
# ----------------------------------------------------------------------
#
# Rotating a TP-sharded gradient leaf through a flat (blocks, n_rot)
# layout forces SPMD to reshard through a reshape — the old partitioner
# handles that by full rematerialization (GiB-scale replicated buffers
# at 15B params).  Instead we rotate along the *unsharded* axes only:
# the sharded dim is transposed to the end (transpose carries sharding;
# it is reshapes that break it), the remaining dims flatten into tiles
# of n_rot, and the FWHT runs along the middle axis.  Every reshape
# splits/merges only unsharded dims => no collective, no remat.

def _fwht_axis1(x: jax.Array) -> jax.Array:
    """Unnormalized FWHT along axis 1 of (A, n, Ns) that never touches
    the other (possibly sharded) axes.

    ``H_n = H_c (x) H_w`` with ``w = min(n, 128)``: splitting axis 1 into
    (c, w), the transform is one contraction with ``H_w`` and one with
    ``H_c`` (matmuls on the MXU).  A log2(n)-pass butterfly of
    reshape/stack would leave size-1/2 minor dims that the TPU pads to
    full (8, 128) tiles — hundreds of times the leaf's bytes in HBM.
    ``HIGHEST`` keeps the +-1 products exact in f32.
    """
    a_dim, n, ns = x.shape
    w = min(n, 128)
    c = n // w
    hp = jax.lax.Precision.HIGHEST
    x = x.reshape(a_dim, c, w, ns)
    x = jnp.einsum("acwn,wv->acvn", x, ref.hadamard_matrix(w), precision=hp)
    if c > 1:
        x = jnp.einsum("acvn,cd->advn", x, ref.hadamard_matrix(c),
                       precision=hp)
    return x.reshape(a_dim, n, ns)


@dataclasses.dataclass(frozen=True)
class NdPlan:
    n_rot: int
    tiles: int          # flattened-unsharded length = tiles * n_rot (padded)
    sharded_dim: int | None
    shape: tuple        # original leaf shape
    m_orig: int         # unpadded flattened-unsharded length


def rademacher_nd(key: jax.Array, plan: "NdPlan") -> jax.Array:
    """Random sign diagonal D, shared by every participant (same key):
    one (n_rot,) vector for all of a leaf's rotation blocks."""
    return jax.random.rademacher(key, (plan.n_rot,), dtype=jnp.float32)


def plan_nd(shape, sharded_dim, n_rot: int = 4096) -> NdPlan:
    m = 1
    for i, d in enumerate(shape):
        if i != sharded_dim:
            m *= d
    while n_rot > 1 and n_rot > m:
        n_rot //= 2
    n_rot = max(n_rot, 2)
    tiles = -(-m // n_rot)
    return NdPlan(n_rot=n_rot, tiles=tiles, sharded_dim=sharded_dim,
                  shape=tuple(shape), m_orig=m)


def to_tiles_nd(g: jax.Array, plan: NdPlan) -> jax.Array:
    """leaf -> (tiles, n_rot, Ns) with only unsharded dims reshaped.

    With no rotation this is also the uncoded wire layout: the plain
    lossy ablation drops rows straight out of it, so what Hadamard buys
    is exactly the delta between the two modes on identical tilings."""
    sd = plan.sharded_dim
    if sd is not None:
        perm = [i for i in range(g.ndim) if i != sd] + [sd]
        g = g.transpose(perm)
        ns = g.shape[-1]
        g = g.reshape(-1, ns)
    else:
        g = g.reshape(-1, 1)
        ns = 1
    pad = plan.tiles * plan.n_rot - plan.m_orig
    if pad:
        g = jnp.pad(g, ((0, pad), (0, 0)))
    return g.reshape(plan.tiles, plan.n_rot, ns)


def from_tiles_nd(t: jax.Array, plan: NdPlan) -> jax.Array:
    """Inverse of :func:`to_tiles_nd` (drops the padding)."""
    sd = plan.sharded_dim
    ns = t.shape[-1]
    g = t.reshape(-1, ns)[: plan.m_orig]
    if sd is None:
        return g.reshape(plan.shape)
    rest = [d for i, d in enumerate(plan.shape) if i != sd]
    g = g.reshape(rest + [ns])
    inv = list(range(len(rest)))
    inv.insert(sd, len(rest))
    return g.transpose(inv)


def fwht_nd(t: jax.Array) -> jax.Array:
    """Normalized (self-inverse) FWHT along the rotation axis of a
    (tiles, n_rot, Ns) block: fwht_nd(fwht_nd(t)) == t."""
    return _fwht_axis1(t) * (t.shape[1] ** -0.5)


def encode_nd(g: jax.Array, signs: jax.Array, plan: NdPlan) -> jax.Array:
    """leaf -> rotated tiles (tiles, n_rot, Ns); signs: (n_rot,)."""
    t = to_tiles_nd(g.astype(jnp.float32), plan)
    return fwht_nd(t * signs[None, :, None])


def decode_nd(tiles_sum: jax.Array, counts: jax.Array, signs: jax.Array,
              plan: NdPlan, *, total_peers: int = 1) -> jax.Array:
    """Inverse of :func:`encode_nd` over *summed received* tiles.

    ``counts`` (n_rot,): how many of the ``total_peers`` expected
    contributions arrived per wire row (rows with 0 arrivals hold
    zeros).  Two unbiasing stages, both exact in expectation and no-ops
    when nothing was lost: scale row j by total_peers/counts[j] so each
    present row estimates the full-peer sum, then every present row by
    n_rot/k (k = rows with any arrival) so the inverse rotation of the
    zero-filled coordinates is unbiased."""
    c = counts[None, :, None]
    safe = jnp.maximum(c, 1.0)
    est = jnp.where(c > 0, tiles_sum * (total_peers / safe), 0.0)
    k = jnp.sum(counts > 0)
    est = est * jnp.where(k > 0, plan.n_rot / jnp.maximum(k, 1), 0.0)
    return from_tiles_nd(fwht_nd(est) * signs[None, :, None], plan)


def one_peer_colscale(mask: jax.Array, plan: NdPlan) -> jax.Array:
    """decode_nd's two unbias stages for one peer as one (n_rot,) f32
    vector: ``mask[j] * n_rot / k``, 0 everywhere when ``k == 0``."""
    k = jnp.sum(mask)
    return mask.astype(jnp.float32) * jnp.where(
        k > 0, plan.n_rot / jnp.maximum(k, 1), 0.0)


def roundtrip_nd(g: jax.Array, signs: jax.Array, colscale: jax.Array,
                 plan: NdPlan) -> jax.Array:
    """One peer's ``decode_nd(encode_nd(g) * mask, ...)`` of a leaf whose
    tiles are flat rows (``plan.sharded_dim is None``, ``n_rot >= 128``)
    as one Pallas kernel (``ops.coded_roundtrip``), in the leaf's dtype;
    ``colscale`` is :func:`one_peer_colscale` of the mask."""
    rows = ops.coded_roundtrip(to_tiles_nd(g, plan)[..., 0], signs, colscale)
    # The barrier keeps the relayout back to the leaf's shape here, in
    # the leaf's dtype.  Without it XLA sinks the reshape into the
    # consumers: AdamW then updates its f32 moments in the (tiles,
    # n_rot) layout and relayouts those, twice the bytes (+20 ms a step
    # for qwen2-0.5b on a v5e).
    return jax.lax.optimization_barrier(from_tiles_nd(rows[..., None], plan))


# ----------------------------------------------------------------------
# XOR parity (exact single-loss recovery per group)
# ----------------------------------------------------------------------

def xor_parity_encode(chunks: jax.Array) -> jax.Array:
    """chunks (g, m) float32 -> parity chunk (m,) via bitwise XOR."""
    bits = jax.lax.bitcast_convert_type(chunks, jnp.int32)
    parity = jax.lax.reduce(bits, jnp.int32(0), jax.lax.bitwise_xor, (0,))
    return jax.lax.bitcast_convert_type(parity, jnp.float32)


def xor_parity_decode(chunks: jax.Array, parity: jax.Array,
                      arrived: jax.Array) -> jax.Array:
    """Recover at most one lost chunk in the group.

    ``chunks`` (g, m) with lost rows zeroed, ``arrived`` (g,) bool.
    If exactly one row is lost it is reconstructed exactly; with zero
    losses the input is returned unchanged; with >1 losses the lost rows
    stay zero (decoder falls back to statistical tolerance).
    """
    n_lost = jnp.sum(~arrived)
    bits = jax.lax.bitcast_convert_type(chunks, jnp.int32)
    # Zeroed-by-mask rows can carry -0.0 (sign bit set) — scrub them so
    # lost rows contribute true zero bits to the XOR.
    bits = jnp.where(arrived[:, None], bits, 0)
    pbits = jax.lax.bitcast_convert_type(parity, jnp.int32)
    xor_all = jax.lax.reduce(bits, jnp.int32(0), jax.lax.bitwise_xor, (0,))
    recovered = jax.lax.bitwise_xor(xor_all, pbits)          # = missing row
    rec_f = jax.lax.bitcast_convert_type(recovered, jnp.float32)
    fill = jnp.where((n_lost == 1) & ~arrived[:, None], rec_f[None, :], 0.0)
    return jnp.where(arrived[:, None], chunks, fill)


# ----------------------------------------------------------------------
