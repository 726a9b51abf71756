"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def hadamard_matrix(n: int, dtype=jnp.float32) -> jax.Array:
    """Sylvester Hadamard matrix H_n (unnormalized, entries +-1)."""
    assert _is_pow2(n), n
    h = jnp.ones((1, 1), dtype=dtype)
    while h.shape[0] < n:
        h = jnp.block([[h, h], [h, -h]])
    return h


def fwht(x: jax.Array) -> jax.Array:
    """Unnormalized fast Walsh-Hadamard transform along the last axis.

    Equivalent to ``x @ hadamard_matrix(n)`` (H is symmetric).
    """
    n = x.shape[-1]
    assert _is_pow2(n), n
    orig_shape = x.shape
    x = x.reshape(-1, n)
    m = 1
    while m < n:
        x = x.reshape(-1, n // (2 * m), 2, m)
        a = x[:, :, 0, :]
        b = x[:, :, 1, :]
        x = jnp.stack([a + b, a - b], axis=2).reshape(-1, n)
        m *= 2
    return x.reshape(orig_shape)


def quantize_int8(x: jax.Array, noise: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row absmax int8 stochastic quantization.

    ``noise`` is uniform[0,1) with the same shape as ``x`` (supplied by the
    caller so that the kernel and the oracle consume identical bits).
    Returns (q_int8, scale_per_row).
    """
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    scaled = x / scale
    q = jnp.floor(scaled + noise)              # stochastic rounding
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale[..., None]


def masked_unbias(y_sum: jax.Array, counts: jax.Array, total: int) -> jax.Array:
    """Decode-side unbiasing: scale received sums by total/count (0 where none).

    ``y_sum``  (rows, n): summed received contributions.
    ``counts`` (rows,) or (rows, n): how many contributions arrived.
    """
    if counts.ndim == y_sum.ndim - 1:
        counts = counts[..., None]
    safe = jnp.maximum(counts, 1)
    return jnp.where(counts > 0, y_sum * (total / safe), 0.0)


def coded_roundtrip(x: jax.Array, signs: jax.Array,
                    colscale: jax.Array) -> jax.Array:
    """One peer's coded sync of (rows, n) tiles: rotate each row
    (``signs``, then the normalised FWHT), scale column j by
    ``colscale[j]``, rotate back; in f32, cast to ``x``'s dtype."""
    n = x.shape[-1]
    y = fwht(x.astype(jnp.float32) * signs) * n ** -0.5
    y = fwht(y * colscale) * n ** -0.5
    return (y * signs).astype(x.dtype)
