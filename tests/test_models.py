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
    """The jnp tiled path (gemma2's softcap keeps it off the kernel)."""
    import repro.models.layers as ml
    cfg = C.get_smoke("gemma2-9b")
    p = L.init_attention(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 128, cfg.d_model)) * .3
    pos = jnp.arange(128, dtype=jnp.int32)[None, :]
    old = ml.FLASH_THRESHOLD
    try:
        ml.FLASH_THRESHOLD = 1
        assert L.attention_path(cfg, 128, 128, decode=False, cross=False,
                                default_positions=True) == "tiled"
        flash, _ = L.attention(p, cfg, x, kind="global", positions=pos)
        ml.FLASH_THRESHOLD = 10 ** 12
        dense, _ = L.attention(p, cfg, x, kind="global", positions=pos)
    finally:
        ml.FLASH_THRESHOLD = old
    np.testing.assert_allclose(np.asarray(flash, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=2e-2, atol=2e-4)


# (configuration, s, s_kv, keyword arguments, mesh?) -> path
ATTENTION_PATHS = {
    "granite_cell": ("granite-moe-3b-a800m", 2048, 2048, {}, False, "pallas"),
    "qwen2_cell": ("qwen2-0.5b", 512, 512, {}, False, "dense"),
    "decode": ("granite-moe-3b-a800m", 1, 4096, {"decode": True}, False,
               "dense"),
    "mesh": ("granite-moe-3b-a800m", 2048, 2048, {}, True, "tiled"),
    "softcap": ("gemma2-9b", 2048, 2048, {}, False, "tiled"),
    "local_window": ("granite-moe-3b-a800m", 2048, 2048, {"kind": "local"},
                     False, "tiled"),
    "cross": ("seamless-m4t-medium", 2048, 2048, {"cross": True}, False,
              "tiled"),
    "caller_positions": ("granite-moe-3b-a800m", 2048, 2048,
                         {"default_positions": False}, False, "tiled"),
    "bidirectional": ("granite-moe-3b-a800m", 2048, 2048, {"causal": False},
                      False, "tiled"),
    "untiled_length": ("granite-moe-3b-a800m", 2112, 2112, {}, False,
                       "tiled"),
}


@pytest.mark.parametrize("case", list(ATTENTION_PATHS))
def test_attention_path(case):
    from repro import sharding as shd
    arch, s, s_kv, kw, on_mesh, want = ATTENTION_PATHS[case]
    kw = {"decode": False, "cross": False, "default_positions": True, **kw}
    shd.set_global_mesh(shd.make_mesh((1, 1), ("data", "model"))
                        if on_mesh else None)
    try:
        assert L.attention_path(C.get(arch), s, s_kv, **kw) == want
    finally:
        shd.set_global_mesh(None)


@pytest.mark.parametrize("arch,layers,b,s,want", [
    ("granite-moe-3b-a800m", 6, 2, 2048, "pallas"),
    ("qwen2-0.5b", 24, 8, 512, "dense")])
def test_attention_path_of_the_benchmark_steps(arch, layers, b, s, want,
                                               monkeypatch):
    """Every attention layer of the benchmark cells' training steps, as
    traced at full width (nothing runs): granite's 6 layers at seq 2048
    take the kernel, qwen2's 24 at seq 512 stay dense."""
    import dataclasses
    cfg = dataclasses.replace(C.get(arch), n_layers=layers)
    seen = []

    def spy(*a, **kw):
        seen.append(path(*a, **kw))
        return seen[-1]

    path = L.attention_path
    monkeypatch.setattr(L, "attention_path", spy)
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((b, s), jnp.int32)
             for k in ("tokens", "labels")}
    jax.eval_shape(jax.grad(lambda p, bt: M.lm_loss(p, cfg, bt)[0]),
                   params, batch)
    assert seen and set(seen) == {want}


def test_attention_takes_the_kernel_and_matches_dense(monkeypatch):
    """Long causal self-attention at the default positions runs the
    Pallas kernel (Granite's 1/64 scale, grouped kv heads), and gives
    what the dense path gives, forward and backward."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "FLASH_BLOCKS",
                        {k: (128, 128) for k in ops.FLASH_BLOCKS})
    cfg = C.get_smoke("granite-moe-3b-a800m")
    p = L.init_attention(jax.random.PRNGKey(8), cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 256, cfg.d_model),
                          jnp.bfloat16)

    def run(want_path):
        assert L.attention_path(cfg, 256, 256, decode=False, cross=False,
                                default_positions=True) == want_path
        return L.attention(p, cfg, x)[0], jax.grad(
            lambda p_: jnp.sum(L.attention(p_, cfg, x)[0].astype(
                jnp.float32) ** 2))(p)

    want = run("dense")
    monkeypatch.setattr(L, "FLASH_THRESHOLD", 1)
    got = run("pallas")
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= 2e-2 * np.abs(w).max()


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
