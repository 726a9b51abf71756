"""Pallas TPU kernel: one peer's coded sync of a gradient leaf.

TPU-native design (this is the HW adaptation of the paper's Hadamard
recovery, which OptiReduce runs on GPU with CUDA butterflies):

- The Sylvester Hadamard matrix factors as a Kronecker product,
  ``H_n = H_c (x) H_128`` with ``n = c * 128``.  Cutting each length-``n``
  row into ``c`` lane-aligned chunks of 128, the transform is

      Y_i = sum_k H_c[i, k] * (X_k @ H_128)

  The intra-chunk stage is one dense (rows, 128) x (128, 128) matmul per
  chunk on the MXU; the cross-chunk stage is a log2(c)-pass butterfly of
  whole-chunk adds and subtracts on the VPU.  No op splits the lane
  dimension: every slice is a 128-lane-aligned window of the tile, which
  is what Mosaic lays out natively (an in-kernel reshape of the lane axis
  to ``(a, b)`` with ``b < 128`` is refused by the TPU compiler).

- Grid tiles rows; each kernel instance holds a ``(block_rows, n)`` tile
  plus the (128, 128) Hadamard factor in VMEM.

``coded_roundtrip_pallas`` computes ``D H diag(colscale) H D x`` per
row in one pass over HBM — the leaf is read once and written once in
its own dtype (bf16 for the train step), and no f32 copy of it reaches
HBM.
Per ``(block_rows, n)`` tile in VMEM:

1. multiply by the Rademacher signs in the leaf's dtype (exact);
2. forward transform: per 128-lane chunk a matmul with ``H_128``, then
   the chunk butterfly;
3. multiply column j by ``colscale[j] / n`` (the mask's unbias times
   both ``n^-1/2`` normalisations; ``1/n`` is a power of two);
4. inverse transform, the same two stages;
5. multiply by the signs, cast to the leaf's dtype, store.

``H_128`` is held in bf16: its +-1 entries are exact there, so each
product of a bf16 operand with it is exact and the MXU sums it in f32.
A bf16 operand (step 2 on a bf16 leaf) takes **one** pass.  An f32
operand (step 4 always, step 2 on an f32 leaf) splits exactly into
three bf16 terms, ``hi = bf16(x)``, ``mid = bf16(x - hi)``,
``lo = x - hi - mid`` (8 + 8 + 8 bits cover f32's 24), and takes
**three** passes.  The products are those ``Precision.HIGHEST`` forms
(whose further splits of a +-1 factor are zero); only the order of the
f32 sums differs.  So a bf16 leaf costs 4 MXU passes of 128 MACs per
element and two VPU butterflies; ``coding.encode_nd``/``decode_nd``
take four HIGHEST contractions of 6 passes each.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref

LANES = 128


def _butterfly(chunks):
    """The cross-chunk stage ``H_c``: log2(c) passes of whole-chunk adds
    and subtracts on the VPU (in place on the list, which it returns)."""
    half = 1
    while half < len(chunks):
        for i in range(0, len(chunks), 2 * half):
            for j in range(i, i + half):
                a, b = chunks[j], chunks[j + half]
                chunks[j], chunks[j + half] = a + b, a - b
        half *= 2
    return chunks


def _exact_dot(x, h):
    """``x @ h`` for a bf16 ``h`` of +-1 entries, every product exact and
    summed in f32: one bf16 MXU pass when ``x`` is bf16; otherwise ``x``
    (as f32) splits exactly into three bf16 terms, hi + mid + lo, and
    takes three passes."""
    if x.dtype == jnp.bfloat16:
        return jnp.dot(x, h, preferred_element_type=jnp.float32)
    x = x.astype(jnp.float32)
    out = None
    for _ in range(3):
        term = x.astype(jnp.bfloat16)
        y = jnp.dot(term, h, preferred_element_type=jnp.float32)
        out = y if out is None else out + y
        x = x - term.astype(jnp.float32)
    return out


def _roundtrip_kernel(x_ref, h_ref, signs_ref, colscale_ref, o_ref):
    n = x_ref.shape[1]
    h = h_ref[...]
    sl = [slice(k * LANES, (k + 1) * LANES) for k in range(n // LANES)]
    # forward: signs (exact in the leaf's dtype), H_128 per chunk, H_c
    chunks = _butterfly([_exact_dot(x_ref[:, s] * signs_ref[:, s], h)
                         for s in sl])
    # both n^-1/2 normalisations ride on the column scale: 1/n is a
    # power of two, so this adds no rounding to colscale's own
    chunks = _butterfly([_exact_dot(y * (colscale_ref[:, s] * (1.0 / n)), h)
                         for y, s in zip(chunks, sl)])
    for y, s in zip(chunks, sl):
        o_ref[:, s] = (y * signs_ref[:, s].astype(jnp.float32)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def coded_roundtrip_pallas(x: jax.Array, signs: jax.Array,
                           colscale: jax.Array, *,
                           block_rows: int | None = None,
                           interpret: bool) -> jax.Array:
    """One peer's coded sync of (rows, n) tiles in one pass over HBM:
    ``D H diag(colscale) H D x`` with ``H`` the normalised n-point
    Hadamard transform along each row and ``D = diag(signs)``; the
    result has ``x``'s dtype.

    ``n`` is a power of two and a multiple of 128; ``block_rows`` is a
    multiple of 16 (default: 256 rows of bf16, 128 of f32, a 2 MiB tile
    at n = 4096 that fits the default scoped VMEM; 256 bf16 rows timed
    fastest of 128/256/512 on a v5e) and is cut to the row count.  The
    grid covers a last, partial block of rows: rows are independent,
    and what the chip reads past the end is never written back.
    """
    rows, n = x.shape
    assert n % LANES == 0 and ref._is_pow2(n), n
    block_rows = min(block_rows or 512 // x.dtype.itemsize, rows)
    sign_dtype = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    vec = pl.BlockSpec((1, n), lambda i: (0, 0))
    return pl.pallas_call(
        _roundtrip_kernel,
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
                  pl.BlockSpec((LANES, LANES), lambda i: (0, 0)),
                  vec, vec],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        interpret=interpret,
    )(x, ref.hadamard_matrix(LANES, jnp.bfloat16),
      signs.reshape(1, n).astype(sign_dtype),
      colscale.reshape(1, n).astype(jnp.float32))
