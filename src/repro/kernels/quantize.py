"""Pallas TPU kernel: per-row absmax int8 stochastic quantization.

Gradient compression stage (beyond-paper distributed-optimization trick;
composes with the Hadamard rotation a la QSGD).  Elementwise + row
reduction, so the kernel is memory-bound by design: one HBM read of the
f32 tile, one int8 write, one small scale write - a 4x traffic cut on
the collective payload.

Uniform[0,1) rounding noise is passed in as an operand (generated with
jax.random outside) so that oracle and kernel consume identical bits and
the kernel needs no TPU PRNG primitives (keeps interpret-mode parity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, noise_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.floor(x / scale + noise_ref[...].astype(jnp.float32))
    q_ref[...] = jnp.clip(q, -127, 127).astype(jnp.int8)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_int8_pallas(x: jax.Array, noise: jax.Array, *,
                         block_rows: int = 128,
                         interpret: bool):
    rows, n = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    # the per-row scale leaves as a (rows, 1) column: a 1-D block of a
    # longer 1-D array does not match the chip's HBM tiling
    q, scale = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, noise)
    return q, scale[:, 0]
