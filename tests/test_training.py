"""End-to-end training behavior: learning, lossy-parity, checkpoint/restart."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.data.pipeline import DataConfig, MarkovLM, make_source
from repro.optim import adamw
from repro.optim.adamw import OptConfig
from repro.train.train_step import CelerisConfig
from repro.train.trainer import Trainer
from repro.checkpoint import checkpoint as ckpt


def _trainer(tmp=None, celeris=None, seed=0, arch="qwen2-0.5b", **kw):
    cfg = C.get_smoke(arch)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8,
                    seed=1)
    return Trainer(cfg, data_cfg=dc,
                   opt_cfg=OptConfig(lr=1e-3, warmup_steps=10,
                                     total_steps=500),
                   celeris=celeris or CelerisConfig(),
                   ckpt_dir=tmp, seed=seed, **kw)


def test_loss_decreases_on_markov_data():
    h = _trainer().run(30)
    assert h["loss"][-1] < h["loss"][0] - 0.4


def test_data_pipeline_deterministic_and_shardable():
    dc = DataConfig(vocab_size=128, seq_len=16, global_batch=8, seed=3)
    src = MarkovLM(dc)
    a = src.shard_batch(5, 2, 4)
    b = src.shard_batch(5, 2, 4)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # different shards / steps differ
    c = src.shard_batch(5, 3, 4)
    d = src.shard_batch(6, 2, 4)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"], d["tokens"])


def test_optimizer_clips_and_steps():
    params = {"w": jnp.ones((4, 4))}
    st = adamw.init_opt_state(params)
    g = {"w": jnp.full((4, 4), 100.0)}
    cfg = OptConfig(lr=1e-2, clip_norm=1.0, warmup_steps=0)
    newp, newst, m = adamw.apply_updates(params, g, st, cfg)
    assert float(m["grad_norm"]) == pytest.approx(400.0)
    assert bool(jnp.all(newp["w"] < params["w"]))
    assert int(newst["count"]) == 1


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3),
            "b": [jnp.ones((4,)), {"c": jnp.float32(3.5)}]}
    ckpt.save(str(tmp_path), 7, tree, extra={"note": "x"})
    like = jax.tree.map(jnp.zeros_like, tree)
    got, step, extra = ckpt.restore(str(tmp_path), like)
    assert step == 7 and extra["note"] == "x"
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_atomicity(tmp_path):
    tree = {"a": jnp.ones((3,))}
    ckpt.save(str(tmp_path), 1, tree)
    # a crash mid-save leaves a .tmp dir; LATEST still points at step 1
    os.makedirs(tmp_path / "step_2.tmp")
    assert ckpt.latest_step(str(tmp_path)) == 1
    got, step, _ = ckpt.restore(str(tmp_path), {"a": jnp.zeros((3,))})
    assert step == 1


def test_fault_restart_resumes(tmp_path):
    """Simulated node failure: a fresh Trainer resumes from LATEST and
    continues from the checkpointed step."""
    t1 = _trainer(str(tmp_path), ckpt_every=5)
    with pytest.raises(RuntimeError, match="simulated node failure"):
        t1.run(20, simulate_fault_at=12)
    assert ckpt.latest_step(str(tmp_path)) == 10
    t2 = _trainer(str(tmp_path), ckpt_every=5)
    assert t2.start_step == 10
    h = t2.run(5)
    assert len(h["loss"]) == 5 and np.isfinite(h["loss"]).all()


def test_lossy_training_parity_small_drop():
    """Fig.-1 claim at smoke scale: <=5% drop w/ Hadamard recovery stays
    within noise of lossless (single-device: drop applies to MoE path /
    degenerate dp, so this mainly checks plumbing + stability)."""
    h_exact = _trainer(seed=3).run(25)
    h_lossy = _trainer(seed=3, celeris=CelerisConfig(
        mode="lossy_hadamard")).run(25)
    assert abs(h_lossy["loss"][-1] - h_exact["loss"][-1]) < 0.3


def test_trainer_timeout_adapts():
    t = _trainer(celeris=CelerisConfig(mode="lossy_hadamard"))
    h = t.run(10)
    assert all(0.5 <= x <= 8.0 for x in h["timeout"])


def test_train_step_microbatched_matches_full():
    """Gradient accumulation must give the same update as one batch."""
    from repro.train import train_step as ts
    cfg = C.get_smoke("qwen2-0.5b")
    src = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                 global_batch=8, seed=5))
    batch = {k: jnp.asarray(v) for k, v in src.global_batch(0).items()}
    key = jax.random.PRNGKey(0)
    s1 = ts.init_state(key, cfg)
    s2 = jax.tree.map(jnp.copy, s1)
    f1 = ts.make_train_step(cfg, None, OptConfig(lr=1e-3), donate=False)
    f2 = ts.make_train_step(cfg, None, OptConfig(lr=1e-3), donate=False,
                            microbatches=4)
    o1, m1 = f1(s1, batch, key, jnp.float32(0))
    o2, m2 = f2(s2, batch, key, jnp.float32(0))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-3)
    for a, b in zip(jax.tree.leaves(o1["params"]),
                    jax.tree.leaves(o2["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=3e-2, atol=3e-4)


def _smoke_params_and_grads():
    from repro.models import model as M
    from repro.train import train_step as ts
    cfg = C.get_smoke("qwen2-0.5b")
    src = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                 global_batch=4, seed=2))
    batch = {k: jnp.asarray(v) for k, v in src.global_batch(0).items()}
    params = ts.init_state(jax.random.PRNGKey(0), cfg)["params"]
    grads = jax.grad(lambda p: M.lm_loss(p, cfg, batch)[0])(params)
    return params, grads


def test_fused_coded_sync_matches_xla_encode_decode(monkeypatch):
    """One device, lossy_hadamard: the one-kernel sync of each coded leaf
    gives the gradients of the XLA encode_nd -> mask -> decode_nd path
    from the same key and drop rate, to one bf16 rounding (f32 leaves:
    to f32 rounding); leaves too small to code pass through."""
    from repro.train import train_step as ts
    params, grads = _smoke_params_and_grads()
    # 64: coded leaves of n_rot 128 to 4096, bf16 and f32 (norm scales),
    # padded tiles among them (bq: 576 elements in two tiles of 512)
    plans = ts._leaf_plans(params, CelerisConfig(mode="lossy_hadamard",
                                                 min_coded_size=64), None)
    assert all(ts._fused(p) for p in plans if p is not None)
    key, drop = jax.random.PRNGKey(7), jnp.float32(0.1)

    def sync():
        return jax.jit(lambda g: ts._emulate_coded_one(g, plans, key,
                                                       drop))(grads)

    got, frac = sync()
    monkeypatch.setattr(ts, "_fused", lambda plan: False)
    want, frac_xla = sync()
    assert float(frac) == float(frac_xla) < 1.0
    for g, a, b, plan in zip(jax.tree.leaves(grads), jax.tree.leaves(got),
                             jax.tree.leaves(want), plans):
        assert a.dtype == b.dtype == g.dtype and a.shape == g.shape
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if plan is None:
            np.testing.assert_array_equal(a, np.asarray(g, np.float32))
            continue
        f32 = 4e-7 * np.sqrt(plan.n_rot) * np.abs(b).max()
        cast = 2.0 ** -7 * np.abs(b) if g.dtype == jnp.bfloat16 else 0.0
        assert np.all(np.abs(a - b) <= f32 + cast), plan


@pytest.mark.parametrize("mode,min_coded_size,mesh_axes,want", [
    ("lossy_hadamard", 65536, None, "all_fused"),
    ("hierarchical", 65536, None, "all_fused"),
    ("lossy_hadamard", 32, None, "narrow_xla"),   # bk, bv: n_rot 32
    ("lossy_hadamard", 65536, ("data", "model"), "all_xla"),
    ("exact", 65536, None, "none"),
])
def test_coded_sync_paths(mode, min_coded_size, mesh_axes, want):
    """coded_sync_paths splits the coded leaves by the path the step's
    sync takes: the kernel on one device where n_rot >= 128, XLA for
    narrower leaves and on a dp mesh, none without coding."""
    from repro.train import train_step as ts
    cfg = C.get_smoke("qwen2-0.5b")
    shapes = jax.eval_shape(lambda k: ts.init_state(k, cfg),
                            jax.random.PRNGKey(0))["params"]
    mesh = (jax.make_mesh((1, 1), mesh_axes) if mesh_axes else None)
    cel = CelerisConfig(mode=mode, min_coded_size=min_coded_size)
    got = ts.coded_sync_paths(shapes, cel, mesh)
    plans = [p for p in ts._leaf_plans(shapes, cel, mesh) if p is not None]
    narrow = sum(p.n_rot < 128 for p in plans)
    expect = {"all_fused": {"fused": len(plans), "xla": 0},
              "narrow_xla": {"fused": len(plans) - 2, "xla": 2},
              "all_xla": {"fused": 0, "xla": len(plans)},
              "none": {"fused": 0, "xla": 0}}[want]
    assert got == expect and len(plans) > 2
    assert narrow == (2 if want == "narrow_xla" else 0)
