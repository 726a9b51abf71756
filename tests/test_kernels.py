"""Pallas kernels vs pure-jnp oracles: the coded round trip and flash
attention."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

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
