"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import functools

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:                     # container lacks hypothesis
    from _propcheck import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(8, 128), (3, 256), (100, 4096), (1, 2), (16, 1024), (257, 512)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("rows,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fwht_matches_oracle(rows, n, dtype):
    x = jax.random.normal(jax.random.PRNGKey(rows * n), (rows, n), dtype)
    got = ops.fwht(x)
    want = ref.fwht(x)
    tol = 1e-4 if dtype == jnp.float32 else 8e-2 * np.sqrt(n)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_fwht_matches_hadamard_matmul():
    n = 256
    x = jax.random.normal(jax.random.PRNGKey(0), (5, n))
    h = ref.hadamard_matrix(n)
    np.testing.assert_allclose(np.asarray(ops.fwht(x)), np.asarray(x @ h),
                               rtol=1e-4, atol=1e-3)


@hypothesis.given(st.integers(1, 40), st.integers(1, 9))
@hypothesis.settings(max_examples=12, deadline=None)
def test_fwht_involution(rows, log_n):
    """H(H(x)) = n * x  (Hadamard is an involution up to scale)."""
    n = 1 << log_n
    x = jax.random.normal(jax.random.PRNGKey(rows + log_n), (rows, n))
    y = ops.fwht(ops.fwht(x)) / n
    np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                               rtol=2e-4, atol=2e-4)


@hypothesis.given(st.integers(1, 6))
@hypothesis.settings(max_examples=6, deadline=None)
def test_fwht_orthogonality(log_n):
    """Parseval: ||Hx||^2 = n ||x||^2."""
    n = 1 << log_n
    x = jax.random.normal(jax.random.PRNGKey(log_n), (4, n))
    lhs = jnp.sum(jnp.square(ops.fwht(x)), -1)
    rhs = n * jnp.sum(jnp.square(x), -1)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-4)


@pytest.mark.parametrize("rows,n", [(8, 128), (64, 512), (3, 64)])
def test_quantize_matches_oracle(rows, n):
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (rows, n)) * 3
    noise = jax.random.uniform(jax.random.fold_in(key, 1), (rows, n))
    q1, s1 = ops.quantize_int8(x, noise)
    q2, s2 = ref.quantize_int8(x, noise)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 256))
    noise = jax.random.uniform(jax.random.PRNGKey(3), (16, 256))
    q, s = ops.quantize_int8(x, noise)
    err = jnp.abs(ops.dequantize_int8(q, s) - x)
    # absmax/127 quantum bound per row
    bound = (jnp.max(jnp.abs(x), -1) / 127.0 * 1.001)[:, None]
    assert bool(jnp.all(err <= bound + 1e-6))


@pytest.mark.parametrize("rows,n", [(8, 128), (32, 64)])
def test_masked_unbias_matches_oracle(rows, n):
    y = jax.random.normal(jax.random.PRNGKey(4), (rows, n))
    c = jax.random.randint(jax.random.PRNGKey(5), (rows,), 0, 5).astype(
        jnp.float32)
    got = ops.masked_unbias(y, c, total=4)
    want = ref.masked_unbias(y, c, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ------------------------------------------------ fused rotate+quantize

@pytest.mark.parametrize("rows,n", [(8, 128), (3, 256), (100, 1024)])
def test_fwht_quantize_fused_matches_unfused_pallas(rows, n):
    """The fused kernel's rotate stage is the same two-matmul body as
    fwht_pallas, so fused == (pallas fwht -> pallas quantize) exactly."""
    key = jax.random.PRNGKey(rows + n)
    x = jax.random.normal(key, (rows, n))
    signs = jax.random.rademacher(jax.random.fold_in(key, 1), (n,),
                                  dtype=jnp.float32)
    noise = jax.random.uniform(jax.random.fold_in(key, 2), (rows, n))
    q1, s1 = ops.fwht_quantize(x, noise, signs=signs, scale=n ** -0.5)
    y = ops.fwht(x, signs=signs, scale=n ** -0.5)
    q2, s2 = ops.quantize_int8(y, noise)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-7)


def test_fwht_quantize_matches_oracle_dequantized():
    """Against the jnp oracle pair the int8 codes may differ by 1 where
    the butterfly vs matmul rotation differs at f32 ulp; the
    dequantized payloads agree to quantization-step tolerance."""
    rows, n = (16, 512)
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (rows, n))
    noise = jax.random.uniform(jax.random.fold_in(key, 2), (rows, n))
    q1, s1 = ops.fwht_quantize(x, noise, scale=n ** -0.5)
    q2, s2 = ops.fwht_quantize(x, noise, scale=n ** -0.5,
                               use_pallas=False)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5)
    d1 = np.asarray(ops.dequantize_int8(q1, s1))
    d2 = np.asarray(ops.dequantize_int8(q2, s2))
    step = np.asarray(s2)[:, None]
    assert np.all(np.abs(d1 - d2) <= 1.001 * step)


def test_encode_quantized_roundtrip():
    """encode_quantized -> dequantize_wire -> decode recovers the
    payload to quantization tolerance when nothing is dropped."""
    from repro.core import coding
    code = coding.plan(1000, n_rot=256)
    key = jax.random.PRNGKey(11)
    signs = coding.rademacher(jax.random.fold_in(key, 0), code)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1000,))
    q_wire, scales = coding.encode_quantized(
        x, signs, code, jax.random.fold_in(key, 2))
    assert q_wire.dtype == jnp.int8 and q_wire.shape == code.wire_shape
    wire = coding.dequantize_wire(q_wire, scales)
    counts = jnp.ones(code.n_rot)
    out = coding.decode(wire, counts, signs, code, total_peers=1)
    # absmax/127 per block, rotated back: bound the error loosely
    tol = float(jnp.max(scales)) * np.sqrt(code.n_rot) * 1.5
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=tol)
    assert float(jnp.max(jnp.abs(out - x))) < 0.2


# ------------------------------------------------ fused coded round trip

@pytest.mark.parametrize("dtype,rows,n,drop", [
    (jnp.float32, 40, 128, 0.1),      # 40 rows: 2.5 blocks of 16
    (jnp.bfloat16, 40, 512, 0.1),
    (jnp.bfloat16, 20, 4096, 0.1),
    (jnp.float32, 24, 4096, 0.1),
    (jnp.bfloat16, 40, 512, 1.0),     # every row lost: zeros
    (jnp.float32, 40, 512, 0.0),      # none lost: the input back
    (jnp.bfloat16, 16, 4096, 0.0),
])
def test_coded_roundtrip_matches_encode_mask_decode(dtype, rows, n, drop):
    """ops.coded_roundtrip is decode_nd(encode_nd(x) * mask, mask) of one
    peer: the same f32 arithmetic up to summation order, then the cast
    to x's dtype (one rounding of bf16 apart at most)."""
    from repro.core import coding
    key = jax.random.PRNGKey(rows * n)
    x = jax.random.normal(key, (rows, n), dtype)
    plan = coding.plan_nd((rows * n,), None, n)
    signs = coding.rademacher_nd(jax.random.fold_in(key, 1), plan)
    mask = jax.random.uniform(jax.random.fold_in(key, 2), (n,)) >= drop
    got = ops.coded_roundtrip(x, signs, coding.one_peer_colscale(mask, plan),
                              block_rows=16)
    tiles = coding.encode_nd(x, signs, plan)
    want = coding.decode_nd(tiles * mask[None, :, None],
                            mask.astype(jnp.float32), signs,
                            plan).reshape(rows, n)
    oracle = np.asarray(ref.coded_roundtrip(
        x, signs, coding.one_peer_colscale(mask, plan)), np.float32)
    assert got.dtype == dtype and got.shape == (rows, n)
    got, want = np.asarray(got, np.float32), np.asarray(want)
    f32 = 4e-7 * np.sqrt(n) * np.abs(want).max()
    cast = 2.0 ** -8 * np.abs(want) if dtype == jnp.bfloat16 else 0.0
    assert np.all(np.abs(got - want) <= f32 + cast)
    assert np.all(np.abs(oracle - want) <= f32 + cast)
    if drop == 1.0:
        assert not np.any(got)
    if drop == 0.0:
        np.testing.assert_allclose(got, np.asarray(x, np.float32),
                                   rtol=2.0 ** -7, atol=f32)


def _dense_causal_attention(q, k, v, scale):
    """The model's dense path: grouped queries against unrepeated kv,
    float32 scores and softmax, bf16 probabilities into P.V."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, s, kv, h // kv, d)
    logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    logits = jnp.where(causal, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bkrqs,bskd->bqkrd", p, v).reshape(b, s, h, d)


@pytest.mark.parametrize("scale", [1 / 64, 64 ** -0.5])
def test_flash_attention_matches_dense(scale, monkeypatch):
    """The Pallas flash kernel against the dense path, forward and the
    gradients of q, k and v: 6 query heads over 2 kv heads, head_dim 64,
    two 128-row tiles each way (one skipped above the diagonal)."""
    monkeypatch.setattr(ops, "FLASH_BLOCKS",
                        {k: (128, 128) for k in ops.FLASH_BLOCKS})
    b, s, h, kv, d = 2, 256, 6, 2, 64
    kq, kk, kv_, kc = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16) * 4
    k = jax.random.normal(kk, (b, s, kv, d), jnp.bfloat16) * 4
    v = jax.random.normal(kv_, (b, s, kv, d), jnp.bfloat16)
    cot = jax.random.normal(kc, (b, s, h, d), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * cot)

    flash = functools.partial(ops.flash_attention, scale=scale)
    dense = functools.partial(_dense_causal_attention, scale=scale)
    got, want = jax.jit(flash)(q, k, v), jax.jit(dense)(q, k, v)
    assert got.shape == want.shape and got.dtype == jnp.bfloat16
    grads = [jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2)))(q, k, v)
             for fn in (flash, dense)]
    for g, w in [(got, want)] + list(zip(*grads)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= 2e-2 * np.abs(w).max()
