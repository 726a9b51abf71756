"""Per-arch smoke tests (reduced configs) + layer-level equivalences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import layers as L
from repro.models import model as M
from repro.models import rglru as RG
from repro.models import xlstm as XL


def _batch(cfg, key, b=2, s=32):
    batch = {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
    }
    if cfg.frontend == "vision_stub":
        batch["image_embeds"] = jax.random.normal(
            key, (b, cfg.n_frontend_tokens, cfg.frontend_dim))
    if cfg.frontend == "audio_stub":
        batch["frame_embeds"] = jax.random.normal(key, (b, s,
                                                        cfg.frontend_dim))
    return batch


@pytest.mark.parametrize("arch", C.ARCHS)
def test_smoke_forward_train_step(arch):
    """One forward + one grad step on CPU: shapes right, nothing NaN."""
    cfg = C.get_smoke(arch)
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    batch = _batch(cfg, key)
    logits, _, _, _ = M.forward(params, cfg, batch)
    exp_s = batch["tokens"].shape[1] + (
        cfg.n_frontend_tokens if cfg.frontend == "vision_stub" else 0)
    assert logits.shape == (2, exp_s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    loss, (nll, aux, _) = M.lm_loss(params, cfg, batch)
    g = jax.grad(lambda p: M.lm_loss(p, cfg, batch)[0])(params)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(g))


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma2-9b",
                                  "recurrentgemma-9b", "xlstm-350m",
                                  "seamless-m4t-medium"])
def test_prefill_decode_consistency(arch):
    """Greedy decode after prefill matches teacher-forced full forward."""
    cfg = C.get_smoke(arch)
    key = jax.random.PRNGKey(1)
    params = M.init_params(key, cfg)
    b, s = 2, 24
    batch = _batch(cfg, key, b, s)
    memory = None
    if cfg.is_encdec:
        memory = M._encode(params, cfg, batch)
    full, _, _, _ = M.forward(params, cfg, {"tokens": batch["tokens"],
                                            **({"frame_embeds":
                                                batch["frame_embeds"]}
                                               if cfg.is_encdec else {})},
                              memory=memory)

    caches = M.init_caches(cfg, b, s + 4)
    pre, caches, _, _ = M.forward(
        params, cfg, {"tokens": batch["tokens"][:, :s - 1]}, caches=caches,
        memory=memory,
        positions=jnp.arange(s - 1, dtype=jnp.int32)[None, :])
    dec, caches, _, _ = M.forward(
        params, cfg, {"tokens": batch["tokens"][:, s - 1:s]},
        caches=caches, cache_index=jnp.int32(s - 1), memory=memory,
        positions=jnp.full((b, 1), s - 1, jnp.int32))
    off = cfg.n_frontend_tokens if cfg.frontend == "vision_stub" else 0
    want = np.asarray(full[:, off + s - 1], np.float32)
    got = np.asarray(dec[:, 0], np.float32)
    scale = max(1.0, np.abs(want).max())
    assert np.abs(got - want).max() / scale < 0.05, arch


def test_local_attention_masks_window():
    cfg = C.get_smoke("gemma2-9b")
    key = jax.random.PRNGKey(2)
    p = L.init_attention(key, cfg)
    x = jax.random.normal(key, (1, 128, cfg.d_model))
    pos = jnp.arange(128, dtype=jnp.int32)[None, :]
    out_l, _ = L.attention(p, cfg, x, kind="local", positions=pos)
    # perturb a token far outside the window of the last query
    x2 = x.at[:, 0].add(10.0)
    out_l2, _ = L.attention(p, cfg, x2, kind="local", positions=pos)
    # last position (window=64) must not see position 0
    np.testing.assert_allclose(np.asarray(out_l[0, -1]),
                               np.asarray(out_l2[0, -1]), atol=1e-5)


def test_partial_rope_rotates_half():
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 2, 16))
    pos = jnp.arange(8, dtype=jnp.int32)[None, :]
    cos, sin, rot = L.rope_tables(pos, 16, 10_000.0, 0.5)
    assert rot == 8
    y = L.apply_rope(x, cos, sin, rot)
    # pass-through half untouched
    np.testing.assert_array_equal(np.asarray(y[..., 8:]),
                                  np.asarray(x[..., 8:]))
    # rotated half differs for pos > 0
    assert np.abs(np.asarray(y[0, 1:, :, :8] - x[0, 1:, :, :8])).max() > 1e-3


def test_flash_equals_dense():
    import repro.models.layers as ml
    cfg = C.get_smoke("gemma2-9b")
    p = L.init_attention(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 128, cfg.d_model)) * .3
    pos = jnp.arange(128, dtype=jnp.int32)[None, :]
    old = ml.FLASH_THRESHOLD
    try:
        ml.FLASH_THRESHOLD = 1
        flash, _ = L.attention(p, cfg, x, kind="global", positions=pos)
        ml.FLASH_THRESHOLD = 10 ** 12
        dense, _ = L.attention(p, cfg, x, kind="global", positions=pos)
    finally:
        ml.FLASH_THRESHOLD = old
    np.testing.assert_allclose(np.asarray(flash, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=2e-2, atol=2e-4)


def test_mlstm_chunkwise_equals_sequential():
    cfg = C.get_smoke("xlstm-350m")
    p = XL.init_mlstm(jax.random.PRNGKey(6), cfg)
    b, s = 2, 128
    x = jax.random.normal(jax.random.PRNGKey(7), (b, s, cfg.d_model)) * .2
    hh = cfg.n_heads
    u = x @ p["w_up"]
    di = u.shape[-1]
    dh = di // hh
    q = (u @ p["wq"]).reshape(b, s, hh, dh) * dh ** -0.5
    k = (u @ p["wk"]).reshape(b, s, hh, dh) * dh ** -0.5
    v = (u @ p["wv"]).reshape(b, s, hh, dh)
    g = u.astype(jnp.float32) @ p["w_if"] + p["b_if"]
    logi, logf = g[..., :hh], jax.nn.log_sigmoid(g[..., hh:])
    z = jnp.zeros
    c0, n0, m0 = (z((b, hh, dh, dh)), z((b, hh, dh)), z((b, hh)))
    seq, _ = XL._mlstm_seq(q, k, v, logi, logf, c0, n0, m0)
    par, _ = XL.mlstm_parallel(q, k, v, logi, logf, c0, n0, m0)
    np.testing.assert_allclose(np.asarray(par), np.asarray(seq),
                               rtol=1e-4, atol=1e-5)


def test_rglru_chunked_matches_decode_rollout():
    cfg = C.get_smoke("recurrentgemma-9b")
    p = RG.init_rglru(jax.random.PRNGKey(8), cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 1024, cfg.d_model)) * .2
    full, _ = RG.rglru_block(p, cfg, x)     # chunked path (1024 = 2*512)
    cache = RG.init_cache(cfg, 1)
    outs = []
    for t in range(0, 1024, 256):           # unchunked fallback segments
        o, cache = RG.rglru_block(p, cfg, x[:, t:t + 256], cache=cache)
        outs.append(o)
    seq = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(full),
                               rtol=2e-2, atol=2e-3)


def test_ring_cache_wraparound_matches_dense_local():
    """Decode past the window: ring cache must equal dense local attn."""
    cfg = C.get_smoke("gemma2-9b")        # window 64
    p = L.init_attention(jax.random.PRNGKey(10), cfg)
    b, total = 1, 96                       # wraps a 64-slot ring
    x = jax.random.normal(jax.random.PRNGKey(11), (b, total, cfg.d_model))
    pos = jnp.arange(total, dtype=jnp.int32)[None, :]
    dense, _ = L.attention(p, cfg, x, kind="local", positions=pos)

    cache = L.AttnCache(
        k=jnp.zeros((b, 64, cfg.n_kv_heads, cfg.resolved_head_dim),
                    jnp.float32),
        v=jnp.zeros((b, 64, cfg.n_kv_heads, cfg.resolved_head_dim),
                    jnp.float32),
        pos=jnp.full((64,), -1, jnp.int32))
    _, cache = L.attention(p, cfg, x[:, :64], kind="local",
                           positions=pos[:, :64], cache=cache)
    for t in range(64, total):
        out, cache = L.attention(
            p, cfg, x[:, t:t + 1], kind="local",
            positions=jnp.full((b, 1), t, jnp.int32),
            cache=cache, cache_index=jnp.int32(t))
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(dense[:, -1], np.float32),
                               rtol=2e-2, atol=2e-3)
