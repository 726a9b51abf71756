"""Hadamard / XOR recovery invariants (hypothesis property tests): the
ND coder's transform, encode/decode over the leaf layouts the trainer
codes, the wire quantizer, and XOR parity."""
try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:                     # container lacks hypothesis
    from _propcheck import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coding
from repro.core import lossy_collectives as lc
from repro.kernels import ref

# ------------------------------------------------ the transform, fwht_nd

SHAPES = [(8, 128), (3, 256), (100, 4096), (1, 2), (16, 1024), (257, 512)]
DTYPES = [jnp.float32, jnp.bfloat16]


@jax.jit
def _oracle(t):
    """ref.fwht along the rotation axis of (tiles, n, Ns), normalised."""
    n = t.shape[1]
    return jnp.swapaxes(ref.fwht(jnp.swapaxes(t, 1, 2)), 1, 2) * n ** -0.5


@pytest.mark.parametrize("rows,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fwht_matches_oracle(rows, n, dtype):
    """fwht_nd on (rows, n, 2) blocks against the butterfly oracle; a
    bf16 leaf enters in f32, as encode_nd casts it."""
    t = jax.random.normal(jax.random.PRNGKey(rows * n), (rows, n, 2),
                          dtype).astype(jnp.float32)
    got = coding.fwht_nd(t)
    assert got.shape == t.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(_oracle(t)),
                               rtol=1e-4, atol=1e-4)


def test_fwht_matches_hadamard_matmul():
    n = 256
    t = jax.random.normal(jax.random.PRNGKey(0), (5, n, 3))
    h = ref.hadamard_matrix(n)
    want = jnp.einsum("anm,nv->avm", t, h,
                      precision=jax.lax.Precision.HIGHEST) / 16.0
    np.testing.assert_allclose(np.asarray(coding.fwht_nd(t)),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


@hypothesis.given(st.integers(1, 40), st.integers(1, 9))
@hypothesis.settings(max_examples=12, deadline=None)
def test_fwht_involution(rows, log_n):
    """The normalised transform is its own inverse."""
    n = 1 << log_n
    t = jax.random.normal(jax.random.PRNGKey(rows + log_n), (rows, n, 2))
    twice = jax.jit(lambda t: coding.fwht_nd(coding.fwht_nd(t)))(t)
    np.testing.assert_allclose(np.asarray(twice), np.asarray(t),
                               rtol=2e-4, atol=2e-4)


@hypothesis.given(st.integers(1, 6))
@hypothesis.settings(max_examples=6, deadline=None)
def test_fwht_orthogonality(log_n):
    """Parseval: the normalised transform keeps every column's norm."""
    n = 1 << log_n
    t = jax.random.normal(jax.random.PRNGKey(log_n), (4, n, 3))
    lhs = jnp.sum(jnp.square(coding.fwht_nd(t)), 1)
    rhs = jnp.sum(jnp.square(t), 1)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-4)


# ------------------------------------------------ encode_nd / decode_nd

# leaf layouts of about n elements: (shape, sharded_dim) — a flat leaf,
# a 2-D weight sharded on its last dim, a (experts, d, f) expert stack
# sharded on the expert dim
LAYOUTS = {
    "flat": lambda n: ((n,), None),
    "2d_sharded_1": lambda n: ((-(-n // 4), 4), 1),
    "expert_sharded_0": lambda n: ((4, -(-n // 16), 4), 0),
}


def _leaf(layout, n, key):
    shape, sd = LAYOUTS[layout](n)
    plan = coding.plan_nd(shape, sd)
    return jax.random.normal(key, shape), plan


def _decoder(signs, plan):
    """decode_nd of the tiles that one peer's mask lets through."""
    return jax.jit(lambda t, m: coding.decode_nd(
        t * m[None, :, None], m.astype(jnp.float32), signs, plan))


@pytest.mark.parametrize("layout", LAYOUTS)
@hypothesis.given(st.integers(10, 30000))
@hypothesis.settings(max_examples=15, deadline=None)
def test_lossless_roundtrip(layout, n):
    x, plan = _leaf(layout, n, jax.random.PRNGKey(n))
    signs = coding.rademacher_nd(jax.random.PRNGKey(1), plan)

    @jax.jit
    def roundtrip(x):
        tiles = coding.encode_nd(x, signs, plan)
        return tiles, coding.decode_nd(tiles, jnp.ones(plan.n_rot), signs,
                                       plan)

    tiles, xhat = roundtrip(x)
    assert tiles.shape[:2] == (plan.tiles, plan.n_rot)
    assert xhat.shape == x.shape
    np.testing.assert_allclose(np.asarray(xhat), np.asarray(x),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("layout", LAYOUTS)
@hypothesis.given(st.integers(0, 10_000), st.floats(0.01, 0.3))
@hypothesis.settings(max_examples=10, deadline=None)
def test_unbiasedness(layout, seed, drop):
    """E[decode(masked encode)] == x over mask draws."""
    x, plan = _leaf(layout, 3000, jax.random.PRNGKey(0))
    signs = coding.rademacher_nd(jax.random.PRNGKey(7), plan)
    tiles = coding.encode_nd(x, signs, plan)
    decode = _decoder(signs, plan)
    ests = []
    for i in range(48):
        m = (jax.random.uniform(jax.random.PRNGKey(seed * 100 + i),
                                (plan.n_rot,)) >= drop)
        ests.append(np.asarray(decode(tiles, m)))
    bias = np.mean(ests, 0) - np.asarray(x)
    # bias -> 0 as 1/sqrt(#draws); allow 5 sigma of the estimator std
    std = np.std(ests, 0) / np.sqrt(len(ests))
    assert np.mean(np.abs(bias) <= 5 * std + 1e-3) > 0.97


@pytest.mark.parametrize("layout", LAYOUTS)
def test_error_scales_with_loss(layout):
    x, plan = _leaf(layout, 8192, jax.random.PRNGKey(3))
    signs = coding.rademacher_nd(jax.random.PRNGKey(4), plan)
    tiles = coding.encode_nd(x, signs, plan)
    decode = _decoder(signs, plan)
    errs = []
    for drop in (0.01, 0.05, 0.2):
        m = (jax.random.uniform(jax.random.PRNGKey(5), (plan.n_rot,))
             >= drop)
        xh = decode(tiles, m)
        errs.append(float(jnp.linalg.norm(xh - x) / jnp.linalg.norm(x)))
    assert errs[0] < errs[1] < errs[2]
    assert errs[0] < 0.15


@pytest.mark.parametrize("layout", LAYOUTS)
def test_energy_spreading(layout):
    """A spiky leaf's loss error is spread, not concentrated: after
    losing 10% of wire rows no single coordinate keeps a huge error."""
    shape, sd = LAYOUTS[layout](4096)
    plan = coding.plan_nd(shape, sd)
    x = jnp.zeros(shape).reshape(-1).at[7].set(100.0).reshape(shape)
    signs = coding.rademacher_nd(jax.random.PRNGKey(8), plan)
    tiles = coding.encode_nd(x, signs, plan)
    m = (jax.random.uniform(jax.random.PRNGKey(9), (plan.n_rot,)) >= 0.1)
    xh = _decoder(signs, plan)(tiles, m)
    err = np.abs(np.asarray(xh - x)).reshape(-1)
    assert err[7] < 25.0                          # spike mostly recovered
    assert np.max(np.delete(err, 7)) < 25.0       # no other spike appears


# ------------------------------------------------ the wire quantizer

def _absmax_tiles(shape, key):
    """(tiles, n_rot, Ns) wire tiles whose rows span six orders of
    magnitude, with one all-zero row."""
    x = jax.random.normal(key, shape)
    x = x * jnp.logspace(-3, 3, shape[1])[None, :, None]
    x = x.at[:, 1, :].set(0.0)
    return x, jnp.max(jnp.abs(x), axis=(0, 2))


@pytest.mark.parametrize("shape", [(2, 128, 1), (3, 256, 4), (1, 4096, 2)])
def test_quantize_rows_matches_definition(shape):
    """lossy_psum's quantizer against a numpy statement of it: one
    absmax/127 grid per wire row (1 for an all-zero row), stochastic
    floor, clipped to +-127, int16."""
    key = jax.random.PRNGKey(shape[1])
    x, absmax = _absmax_tiles(shape, key)
    noise = jax.random.uniform(jax.random.fold_in(key, 1), shape)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = lc.quantize_rows(x, scale, noise)
    xs, ns, am = (np.asarray(a, np.float32) for a in (x, noise, absmax))
    sc = np.where(am > 0, am / np.float32(127.0), np.float32(1.0))
    want = np.clip(np.floor(xs / sc[None, :, None] + ns), -127, 127)
    assert q.dtype == jnp.int16 and q.shape == shape
    np.testing.assert_array_equal(np.asarray(q), want.astype(np.int16))
    assert not np.any(np.asarray(q)[:, 1, :])


def test_quantize_rows_error_within_absmax_over_127():
    """Each code is within one step of its value: |q * scale - x| <=
    absmax / 127 on every row, and no code leaves +-127."""
    key = jax.random.PRNGKey(2)
    x, absmax = _absmax_tiles((8, 512, 3), key)
    noise = jax.random.uniform(jax.random.fold_in(key, 1), x.shape)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = lc.quantize_rows(x, scale, noise)
    err = jnp.abs(q.astype(jnp.float32) * scale[None, :, None] - x)
    bound = (absmax / 127.0 * 1.001)[None, :, None]
    assert bool(jnp.all(err <= bound + 1e-30))
    assert int(jnp.max(jnp.abs(q))) <= 127


# ------------------------------------------------ XOR parity

@hypothesis.given(st.integers(2, 16), st.integers(0, 100))
@hypothesis.settings(max_examples=20, deadline=None)
def test_xor_single_loss_exact(g, seed):
    chunks = jax.random.normal(jax.random.PRNGKey(seed), (g, 32))
    parity = coding.xor_parity_encode(chunks)
    lost = seed % g
    arrived = jnp.ones((g,), bool).at[lost].set(False)
    rec = coding.xor_parity_decode(chunks * arrived[:, None], parity, arrived)
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(chunks))


def test_xor_double_loss_falls_back_to_zero():
    chunks = jax.random.normal(jax.random.PRNGKey(1), (6, 16))
    parity = coding.xor_parity_encode(chunks)
    arrived = jnp.ones((6,), bool).at[1].set(False).at[4].set(False)
    rec = coding.xor_parity_decode(chunks * arrived[:, None], parity, arrived)
    assert np.all(np.asarray(rec[1]) == 0) and np.all(np.asarray(rec[4]) == 0)
    np.testing.assert_array_equal(np.asarray(rec[0]), np.asarray(chunks[0]))
