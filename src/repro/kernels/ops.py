"""Public wrappers around the Pallas kernels the program runs: the
coded sync's ``coded_roundtrip``, JAX's megablox grouped matmul and its
splash (flash) attention.

The kernels compile for the chip; on the CPU backend (tests, the
dry-run host) they run in Pallas interpret mode instead, decided per
call from ``jax.default_backend()``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash,
    splash_attention_mask as _splash_mask,
)

from repro.kernels import fwht as _fwht


def _interpret() -> bool:
    """Interpret the kernels only where there is no chip to compile for."""
    return jax.default_backend() == "cpu"


def coded_roundtrip(x: jax.Array, signs: jax.Array, colscale: jax.Array, *,
                    block_rows: int | None = None) -> jax.Array:
    """One peer's coded sync of (rows, n) tiles in one kernel: rotate
    (``signs`` (n,), normalised FWHT), scale column j by ``colscale[j]``
    (n,), rotate back, in x's dtype.  ``n`` is a power of two >= 128.
    Rows need no padding: the kernel's grid takes a partial last block.
    """
    return _fwht.coded_roundtrip_pallas(x, signs, colscale,
                                        block_rows=block_rows,
                                        interpret=_interpret())


GMM_TILE = 512   # rows, contraction and output tile of the grouped matmul


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """Rows of ``lhs`` (m, k), grouped contiguously (group g is the next
    ``group_sizes[g]`` rows), each times its group's (k, n) matrix of
    ``rhs`` (G, k, n): (m, n) in lhs's dtype, accumulated in float32.
    The Pallas grouped matmul of JAX's megablox library, differentiable
    (its backward is two more grouped matmuls); rows are padded to a
    whole number of row tiles.  Rows past the groups' total come out
    unspecified."""
    m, k = lhs.shape
    tm = min(GMM_TILE, -(-m // 8) * 8)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _megablox.gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                        (tm, min(GMM_TILE, k), min(GMM_TILE, rhs.shape[2])),
                        None, None, False, _interpret())
    return out[:m]


# (query rows, key rows) of the flash kernel's forward, dq and dkv tiles:
# the fastest of 256, 512 and 1024 on one TPU v5e at (2, 2048, 24 over 8
# heads, 64), forward and forward + backward
FLASH_BLOCKS = {"fwd": (512, 512), "dq": (1024, 1024), "dkv": (1024, 1024)}


def _flash_blocks(s: int) -> dict:
    return {k: (min(bq, s), min(bk, s)) for k, (bq, bk) in FLASH_BLOCKS.items()}


def flash_attention_fits(s: int) -> bool:
    """Whether ``flash_attention`` takes sequences of length ``s``: every
    tile divides it and spans whole 128-lane rows."""
    return all(t % 128 == 0 and s % t == 0
               for ts in _flash_blocks(s).values() for t in ts)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float) -> jax.Array:
    """Causal self-attention of (B, S, H, D) queries over (B, S, KV, D)
    keys and values, H a multiple of KV (grouped queries): (B, S, H, D)
    in q's dtype.  JAX's Pallas splash attention, forward and backward
    (its dq and dkv kernels): scores and their online softmax stay in
    VMEM in float32, and key blocks above the diagonal are skipped.  The
    score and gradient matmuls take operands in the inputs' dtype with
    float32 accumulation; the forward's P.V runs in float32 (the dense
    path rounds P to v's dtype first).  ``scale`` is folded into q in
    float32 and rounded once (exact where it is a power of two).  ``S``
    has to pass ``flash_attention_fits``."""
    s, h = q.shape[1:3]
    tiles = _flash_blocks(s)
    (fq, fk), (dqq, dqk), (kvq, kvk) = (tiles[p] for p in ("fwd", "dq",
                                                            "dkv"))
    blocks = _splash.BlockSizes(
        block_q=fq, block_kv=fk, block_kv_compute=fk,
        block_q_dq=dqq, block_kv_dq=dqk,
        block_q_dkv=kvq, block_kv_dkv=kvk, block_kv_dkv_compute=kvk)
    mask = _splash_mask.MultiHeadMask([_splash_mask.CausalMask((s, s))] * h)
    kernel = _splash.make_splash_mha_single_device(
        mask, block_sizes=blocks, interpret=_interpret())
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    out = jax.vmap(kernel)(qs.swapaxes(1, 2), k.swapaxes(1, 2),
                           v.swapaxes(1, 2))
    return out.swapaxes(1, 2)
