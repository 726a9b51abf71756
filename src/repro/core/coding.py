"""ML-pipeline loss recovery (paper §III-B, last paragraph).

Celeris ships no transport-layer recovery; instead the framework encodes
collective payloads so that *bounded, partial* loss is absorbed:

**Randomized Hadamard rotation** (a la OptiReduce / Fig. 1):
    encode:  y = (1/sqrt(n)) H D x     per rotation block of width n
    decode:  x_hat = (n/k) (1/sqrt(n)) D H S y   (S = arrival mask, k = |S|)
  which is exactly unbiased (E[x_hat] = x) and lossless when k = n.

**Wire interleaving** — rotation must span *more* than the loss
granularity or a dropped chunk would take a whole rotation block with
it.  After rotating each (B, n) block-row we transpose to (n, B) "wire
layout": network chunk j carries coordinate j of *every* rotation block,
so any lost chunk removes a 1/n coordinate slice from each block and the
unbiased rescale recovers the rest.  This implements the paper's
"critical information ... split across packets for partial recovery".

**XOR parity** — exact recovery of any single lost chunk per parity
group (the paper's lightweight coding alternative for prioritized data,
e.g. activation shards under lossy TP).

All transforms run through the Pallas FWHT kernel (MXU path on TPU);
``use_pallas=False`` routes to the jnp oracle for dry-run lowering.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


@dataclasses.dataclass(frozen=True)
class HadamardCode:
    """Static coding geometry for one flat payload."""
    n_rot: int          # rotation block width (power of two)
    n_blocks: int       # number of rotation blocks  (padded_len = n_rot*n_blocks)
    orig_len: int       # unpadded payload length

    @property
    def padded_len(self) -> int:
        return self.n_rot * self.n_blocks

    @property
    def wire_shape(self) -> tuple[int, int]:
        """(n_rot, n_blocks): wire row j = coordinate j of every block."""
        return (self.n_rot, self.n_blocks)


def plan(orig_len: int, n_rot: int = 4096, block_multiple: int = 1
         ) -> HadamardCode:
    """``block_multiple``: round n_blocks up so the block dim shards
    cleanly over the model axis (keeps the FWHT collective-free)."""
    while n_rot > 1 and n_rot > orig_len:
        n_rot //= 2
    n_rot = max(n_rot, 2)
    n_blocks = -(-orig_len // n_rot)
    n_blocks = -(-n_blocks // block_multiple) * block_multiple
    return HadamardCode(n_rot=n_rot, n_blocks=n_blocks, orig_len=orig_len)


def rademacher(key: jax.Array, code: HadamardCode) -> jax.Array:
    """Random sign diagonal D, shared by every participant (same key).

    One (n_rot,) vector shared across rotation blocks — per-block signs
    would double parameter-scale memory at 15B-model size, and per-block
    unbiasedness holds either way (OptiReduce likewise reuses one
    rotation per chunk).
    """
    return jax.random.rademacher(key, (code.n_rot,), dtype=jnp.float32)


def encode(x: jax.Array, signs: jax.Array, code: HadamardCode, *,
           use_pallas: bool = True, constrain=None) -> jax.Array:
    """flat (orig_len,) -> wire layout (n_rot, n_blocks).

    ``constrain(a, kind)`` (kind in {"blocks","wire"}): optional sharding
    hint applied inside — used by the trainer to keep the block dim on
    the model axis so the FWHT stays collective-free under GSPMD.
    """
    if x.ndim == 2 and x.shape == (code.n_blocks, code.n_rot):
        blocks = x          # pre-blocked (keeps big leaves sharded)
    else:
        x = x.reshape(-1)
        x = jnp.pad(x, (0, code.padded_len - code.orig_len))
        blocks = x.reshape(code.n_blocks, code.n_rot)
    if constrain is not None:
        blocks = constrain(blocks, "blocks")
    # sign-multiply + 1/sqrt(n) normalization fused into the kernel
    # (saves two full HBM round-trips per encode on the Pallas path)
    rot = ops.fwht(blocks, signs=signs, scale=code.n_rot ** -0.5,
                   use_pallas=use_pallas)
    wire = rot.T
    if constrain is not None:
        wire = constrain(wire, "wire")
    return wire


def encode_quantized(x: jax.Array, signs: jax.Array, code: HadamardCode,
                     noise_key: jax.Array, *, use_pallas: bool = True,
                     constrain=None) -> tuple[jax.Array, jax.Array]:
    """:func:`encode` with the wire payload quantized to int8.

    Per rotation block the rotated coordinates are stochastically
    rounded to absmax-scaled int8 (QSGD-style; the rotation's variance
    flattening is exactly what makes a shared per-block scale cheap) —
    a 4x cut in collective wire bytes.  The rotate and quantize stages
    run as ONE fused Pallas kernel (``ops.fwht_quantize``): the rotated
    tile never round-trips through HBM between them.

    Returns ``(q_wire (n_rot, n_blocks) int8, scales (n_blocks,))``;
    :func:`dequantize_wire` restores the f32 wire layout that
    :func:`decode` consumes.
    """
    if x.ndim == 2 and x.shape == (code.n_blocks, code.n_rot):
        blocks = x
    else:
        x = x.reshape(-1)
        x = jnp.pad(x, (0, code.padded_len - code.orig_len))
        blocks = x.reshape(code.n_blocks, code.n_rot)
    if constrain is not None:
        blocks = constrain(blocks, "blocks")
    noise = jax.random.uniform(noise_key, blocks.shape)
    q, scales = ops.fwht_quantize(blocks, noise, signs=signs,
                                  scale=code.n_rot ** -0.5,
                                  use_pallas=use_pallas)
    return q.T, scales


def dequantize_wire(q_wire: jax.Array, scales: jax.Array) -> jax.Array:
    """int8 wire layout (n_rot, n_blocks) -> f32 wire layout."""
    return q_wire.astype(jnp.float32) * scales[None, :]


def decode(wire_sum: jax.Array, counts: jax.Array, signs: jax.Array,
           code: HadamardCode, *, total_peers: int = 1,
           use_pallas: bool = True, constrain=None,
           out_blocks: bool = False) -> jax.Array:
    """Inverse of :func:`encode` over *summed received* wire data.

    ``wire_sum`` (n_rot, n_blocks): per-wire-row sums of the
    contributions that arrived inside the window.  ``counts`` (n_rot,):
    how many of the ``total_peers`` expected contributions arrived per
    row (rows with 0 arrivals hold zeros).

    Two unbiasing stages (both exact in expectation, both no-ops when
    nothing was lost):
      1. peer unbias — scale row r by total_peers/counts[r] so each
         present row estimates the *full-peer* sum of that coordinate;
      2. sampling unbias — scale every present row by n_rot/k
         (k = rows with any arrival) so the inverse rotation of the
         zero-filled coordinate vector is unbiased.
    """
    row_est = ops.masked_unbias(wire_sum, counts, total_peers,
                                use_pallas=use_pallas)       # stage 1
    k = jnp.sum(counts > 0)
    scale = jnp.where(k > 0, code.n_rot / jnp.maximum(k, 1), 0.0)
    rot = row_est.T * scale                                  # stage 2
    if constrain is not None:
        rot = constrain(rot, "blocks")
    blocks = (ops.fwht(rot, scale=code.n_rot ** -0.5, use_pallas=use_pallas)
              * signs[None, :])
    if constrain is not None:
        blocks = constrain(blocks, "blocks")
    if out_blocks:
        return blocks       # (n_blocks, n_rot), caller reshapes in place
    return blocks.reshape(-1)[: code.orig_len]


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
# Convenience: pytree-level encode/decode used by the trainer
# ----------------------------------------------------------------------

def tree_ravel(tree) -> tuple[jax.Array, object]:
    flat, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [(l.shape, l.dtype) for l in flat]
    vec = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in flat])
    return vec, (treedef, shapes)


def tree_unravel(vec: jax.Array, spec) -> object:
    treedef, shapes = spec
    out, off = [], 0
    for shape, dtype in shapes:
        size = 1
        for s in shape:
            size *= s
        out.append(vec[off: off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


# ----------------------------------------------------------------------
# Sharding-aware ND coding (the form the trainer uses at scale)
# ----------------------------------------------------------------------
#
# Rotating a TP-sharded gradient leaf through the flat (n_blocks, n_rot)
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


def _to_tiles(g: jax.Array, plan: NdPlan) -> jax.Array:
    """leaf -> (tiles, n_rot, Ns) with only unsharded dims reshaped."""
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


def _from_tiles(t: jax.Array, plan: NdPlan) -> jax.Array:
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


# Public tile layout (no rotation): the plain-lossy ablation path drops
# wire rows straight out of this layout, so what Hadamard buys is exactly
# the delta between the two modes on identical tilings.
def to_tiles_nd(g: jax.Array, plan: NdPlan) -> jax.Array:
    return _to_tiles(g, plan)


def from_tiles_nd(t: jax.Array, plan: NdPlan) -> jax.Array:
    return _from_tiles(t, plan)


def fwht_nd(t: jax.Array, plan: NdPlan) -> jax.Array:
    """Normalized (self-inverse) FWHT along the rotation axis of a
    (tiles, n_rot, Ns) block: fwht_nd(fwht_nd(t)) == t."""
    return _fwht_axis1(t) * (plan.n_rot ** -0.5)


def encode_nd(g: jax.Array, signs: jax.Array, plan: NdPlan) -> jax.Array:
    """leaf -> rotated tiles (tiles, n_rot, Ns); signs: (n_rot,)."""
    t = _to_tiles(g.astype(jnp.float32), plan)
    t = t * signs[None, :, None]
    return _fwht_axis1(t) * (plan.n_rot ** -0.5)


def decode_nd(tiles_sum: jax.Array, counts: jax.Array, signs: jax.Array,
              plan: NdPlan, *, total_peers: int = 1) -> jax.Array:
    """Inverse of encode_nd over summed received tiles; counts (n_rot,)."""
    c = counts[None, :, None]
    safe = jnp.maximum(c, 1.0)
    est = jnp.where(c > 0, tiles_sum * (total_peers / safe), 0.0)
    k = jnp.sum(counts > 0)
    est = est * jnp.where(k > 0, plan.n_rot / jnp.maximum(k, 1), 0.0)
    est = _fwht_axis1(est) * (plan.n_rot ** -0.5) * signs[None, :, None]
    return _from_tiles(est, plan)


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
    rows = ops.coded_roundtrip(_to_tiles(g, plan)[..., 0], signs, colscale)
    # The barrier keeps the relayout back to the leaf's shape here, in
    # the leaf's dtype.  Without it XLA sinks the reshape into the
    # consumers: AdamW then updates its f32 moments in the (tiles,
    # n_rot) layout and relayouts those, twice the bytes (+20 ms a step
    # for qwen2-0.5b on a v5e).
    return jax.lax.optimization_barrier(_from_tiles(rows[..., None], plan))
