"""Pure-jnp oracles for the Pallas kernels in this package."""
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


def coded_roundtrip(x: jax.Array, signs: jax.Array,
                    colscale: jax.Array) -> jax.Array:
    """One peer's coded sync of (rows, n) tiles: rotate each row
    (``signs``, then the normalised FWHT), scale column j by
    ``colscale[j]``, rotate back; in f32, cast to ``x``'s dtype."""
    n = x.shape[-1]
    y = fwht(x.astype(jnp.float32) * signs) * n ** -0.5
    y = fwht(y * colscale) * n ** -0.5
    return (y * signs).astype(x.dtype)
