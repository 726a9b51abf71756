"""The MoE block's one-device dropless path and counters, the expert
padding, and the scaled-path multipliers of ``ModelConfig``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import model as M
from repro.models import moe as MOE


def _granite(pad=1):
    cfg = C.get_smoke("granite-moe-3b-a800m")
    return dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, expert_pad_multiple=pad))


def _dense_sum(p, cfg, x2d):
    """Every expert on every token, summed with the router's top-k gates
    (zero for the experts not chosen): the block by its definition."""
    logits = jnp.dot(x2d, p["router"], precision="highest")
    e_pad = logits.shape[-1]
    logits = jnp.where(jnp.arange(e_pad) >= cfg.moe.n_experts, -1e30, logits)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_i = jax.lax.top_k(probs, cfg.moe.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x2d.shape[0])[:, None], top_i].set(top_p)
    with jax.default_matmul_precision("highest"):
        a = jnp.einsum("gd,edf->gef", x2d, p["wg"])
        b = jnp.einsum("gd,edf->gef", x2d, p["wi"])
        y = jnp.einsum("gef,efd->ged", jax.nn.silu(a) * b, p["wo"])
    return jnp.einsum("ge,ged->gd", gates, y, precision="highest"), top_i


@pytest.mark.parametrize("pad", [1, 16])
@pytest.mark.parametrize("skew", [False, True])
@pytest.mark.parametrize("on_mesh", [False, True])
def test_dropless_block_equals_dense_per_expert_sum(pad, skew, on_mesh):
    """Every routed row reaches its expert: the block equals the dense
    sum over experts, also when one expert takes most rows, and counts
    nothing dropped; the busiest expert's rows over the mean follow the
    routing.  With no mesh the grouped matmuls are the Pallas kernel;
    on a mesh (here one device's) they are ``jax.lax.ragged_dot``."""
    from repro import sharding as shd
    cfg = _granite(pad)
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, cfg.d_model))
    if skew:   # expert 3 first for every token with a positive mean
        x = jnp.abs(x)
        p["router"] = p["router"].at[:, 3].set(1.0)
    shd.set_global_mesh(shd.make_mesh((1, 1), ("data", "model"))
                        if on_mesh else None)
    try:
        with jax.default_matmul_precision("highest"):
            y, aux, stats = MOE.moe_block(p, cfg, x, routes=True)
    finally:
        shd.set_global_mesh(None)
    want, top_i = _dense_sum(p, cfg, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(stats["moe_routes"]),
                                  np.asarray(top_i))
    counts = np.bincount(np.asarray(top_i).ravel(),
                         minlength=cfg.moe.n_experts)
    g = x.shape[0] * x.shape[1]
    assert float(stats["moe_dropped"]) == 0.0
    assert float(stats["moe_load_max"]) == pytest.approx(
        counts.max() / (g * cfg.moe.top_k / cfg.moe.n_experts))
    if skew:
        # every token picks expert 3: the most rows one expert can get
        assert counts[3] == g == counts.sum() // cfg.moe.top_k
        assert float(stats["moe_load_max"]) == pytest.approx(
            cfg.moe.n_experts / cfg.moe.top_k)
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_capacity_dispatch_counts_its_drops():
    """The expert-parallel path's capacity slots: picks beyond an
    expert's capacity are dropped and counted."""
    cfg = _granite()
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    x2d = jnp.abs(jax.random.normal(jax.random.PRNGKey(2),
                                    (32, cfg.d_model)))
    p["router"] = p["router"].at[:, 5].set(1.0)   # all 32 pick expert 5
    e = MOE.padded_experts(cfg)
    _, _, _, share, dropped = MOE._dispatch_2d(p, cfg, x2d, e, 8)
    assert float(share[5]) == pytest.approx(32 / 64)
    assert float(dropped) >= 32 - 8


def test_expert_padding():
    cfg = _granite(16)
    assert MOE.padded_experts(cfg) == 16
    assert MOE.padded_experts(_granite(1)) == cfg.moe.n_experts
    assert MOE.init_moe(jax.random.PRNGKey(0), cfg)["wg"].shape[0] == 16
    full = C.get("granite-moe-3b-a800m")
    assert full.moe.expert_pad_multiple == 16
    assert MOE.padded_experts(dataclasses.replace(
        full, moe=dataclasses.replace(full.moe, expert_pad_multiple=1))) == 40


def _qwen_loss_and_grads(cfg):
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    (loss, _), g = jax.value_and_grad(M.lm_loss, has_aux=True)(
        params, cfg, batch)
    return [np.asarray(loss)] + [np.asarray(a) for a in jax.tree.leaves(g)]


def test_default_multipliers_leave_results_bit_identical():
    """The multipliers at their defaults (sqrt(d_model), 1/sqrt(head_dim),
    1, 1) are today's dense decoder, bit for bit; set otherwise they
    change the loss."""
    cfg = C.get_smoke("qwen2-0.5b")
    same = dataclasses.replace(
        cfg, embedding_multiplier=cfg.d_model ** 0.5,
        attention_multiplier=cfg.resolved_head_dim ** -0.5,
        residual_multiplier=1.0, logits_scaling=1.0)
    for a, b in zip(_qwen_loss_and_grads(cfg), _qwen_loss_and_grads(same)):
        assert a.tobytes() == b.tobytes()
    for field, value in [("embedding_multiplier", 12.0),
                         ("attention_multiplier", 0.5),
                         ("residual_multiplier", 0.22),
                         ("logits_scaling", 6.0)]:
        other = dataclasses.replace(cfg, **{field: value})
        assert (_qwen_loss_and_grads(other)[0].tobytes()
                != _qwen_loss_and_grads(cfg)[0].tobytes()), field


def test_residual_multiplier_rounds_once():
    """A bf16 branch times 0.22 is the float32 product rounded once to
    bf16, as a bf16 tensor times a Python float is in the published
    model, and not times 0.22 rounded to bf16 (0.2197265625)."""
    cfg = C.get("granite-moe-3b-a800m")
    h = jnp.linspace(-64, 64, 4097, dtype=jnp.float32).astype(jnp.bfloat16)
    got = np.asarray(M._residual(cfg, h).astype(jnp.float32))
    want = np.asarray((h.astype(jnp.float32) * np.float32(0.22))
                      .astype(jnp.bfloat16).astype(jnp.float32))
    assert cfg.residual_multiplier == 0.22
    np.testing.assert_array_equal(got, want)
    rounded = np.asarray(
        (h * jnp.asarray(0.22, jnp.bfloat16)).astype(jnp.float32))
    assert (got != rounded).any()


def test_granite_smoke_carries_the_multipliers():
    cfg = C.get_smoke("granite-moe-3b-a800m")
    full = C.get("granite-moe-3b-a800m")
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 6.0)
    assert cfg.attention_multiplier == 1 / cfg.resolved_head_dim
    assert full.attention_multiplier == 1 / full.resolved_head_dim == 0.015625
    assert (full.rope_theta, full.norm_eps) == (10_000.0, 1e-6)


def test_train_step_reads_the_moe_counters():
    """The step's metrics carry the MoE counters beside its loss; a
    model with no MoE layer has none."""
    from repro.optim.adamw import OptConfig
    from repro.train import train_step as ts
    for name, want in [("granite-moe-3b-a800m", True), ("qwen2-0.5b", False)]:
        cfg = C.get_smoke(name)
        step = ts.make_train_step(cfg, None, OptConfig(warmup_steps=1),
                                  ts.CelerisConfig(mode="exact"),
                                  donate=False)
        state = ts.init_state(jax.random.PRNGKey(0), cfg)
        toks = jnp.zeros((2, 16), jnp.int32)
        _, m = step(state, {"tokens": toks, "labels": toks},
                    jax.random.PRNGKey(1), jnp.float32(0.0))
        assert ({"moe_load_max", "moe_dropped"} <= set(m)) == want
        if want:
            # every token is the same: all pick the same k experts
            assert float(m["moe_load_max"]) == pytest.approx(
                cfg.moe.n_experts / cfg.moe.top_k)
            assert float(m["moe_dropped"]) == 0.0
