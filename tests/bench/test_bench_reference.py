"""The reference's one-chip coded sync against a dense restatement of
its definition: rotate by H D / sqrt(n), keep the received wire rows
scaled by n / (rows received), rotate back."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

from bench.reference.dense_lm import DenseLM, hadamard  # noqa: E402

CFG = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "optimizer": {}}
TRAFFIC = {"mode": "lossy_hadamard",
           "celeris": {"n_rot": 64, "min_coded_size": 100}}


def _dense(g, key, i, drop, n):
    import jax
    signs = np.asarray(jax.random.rademacher(jax.random.fold_in(key, 2 * i),
                                             (n,), dtype=np.float32))
    got = np.asarray(jax.random.uniform(
        jax.random.fold_in(jax.random.fold_in(key, 2 * i + 1), 0), (n,)))
    keep = got >= drop
    h = hadamard(n) / np.sqrt(n)
    flat = g.reshape(-1)
    tiles = -(-flat.size // n)
    t = np.pad(flat, (0, tiles * n - flat.size)).reshape(tiles, n)
    r = (t * signs) @ h.T
    r = np.where(keep, r, 0.0) * (n / keep.sum())
    out = (r @ h.T) * signs
    return out.reshape(-1)[: flat.size].reshape(g.shape)


@pytest.mark.parametrize("drop", [0.0, 0.3])
def test_coded_sync_matches_its_definition(drop):
    import jax
    ref = DenseLM(CFG, TRAFFIC)
    g = np.random.default_rng(3).standard_normal((5, 7, 9)).astype(
        np.float32)
    key = jax.random.PRNGKey(2147483901)
    pl = ref._plan(g.shape)
    assert pl["n_rot"] == 64 and pl["tiles"] == 5
    got = np.asarray(ref._code(g, pl, key, 4, np.float32(drop)))
    want = _dense(g, key, 4, drop, 64)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if drop == 0.0:
        np.testing.assert_allclose(got, g, rtol=1e-4, atol=1e-5)


def test_small_leaves_are_not_coded():
    assert DenseLM(CFG, TRAFFIC)._plan((9, 11)) is None
