"""Pallas TPU kernel: fused decode-side unbiasing.

After a lossy collective, each rotation-block row has a received-count;
the unbiased estimate scales the summed contributions by total/count.
Fusing the scale with the (count>0) select avoids an extra HBM round
trip over the gradient buffer between the collective and the inverse
Hadamard pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _unbias_kernel(y_ref, c_ref, o_ref, *, total: int):
    y = y_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    safe = jnp.maximum(c, 1.0)
    o_ref[...] = jnp.where(c > 0, y * (total / safe), 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("total", "block_rows", "interpret"))
def masked_unbias_pallas(y_sum: jax.Array, counts: jax.Array, *, total: int,
                         block_rows: int = 256, interpret: bool) -> jax.Array:
    rows, n = y_sum.shape
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    # counts ride in as a (rows, 1) column: a 1-D block of a longer 1-D
    # array does not match the chip's HBM tiling
    return pl.pallas_call(
        functools.partial(_unbias_kernel, total=total),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), y_sum.dtype),
        interpret=interpret,
    )(y_sum, counts.reshape(rows, 1))
