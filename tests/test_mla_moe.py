"""DeepSeek-V3's block at smoke widths on the CPU, against the plain
reference ``bench/reference/moonlight.py`` on seeded random weights:
latent attention on each of its three paths, the sigmoid router with
its load-balancing bias, the held share of the experts, the bias update
over two steps, the leading dense layer, and the coded train step
through ``Trainer``; and the parameter counts of the published model
and of one chip's share."""
import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import repro.configs as C  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.train import train_step as ts  # noqa: E402

from bench.reference.moonlight import MoonlightMoE  # noqa: E402

SMOKE = "moonlight-16b-a3b"


def _cfg(**moe):
    cfg = dataclasses.replace(C.get_smoke(SMOKE), dtype="float32")
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe))


def _ref(cfg):
    """The reference for a program ModelConfig (widths as in the
    configuration file)."""
    m, a = cfg.moe, cfg.mla
    held = MOE.held(cfg) or (0, m.n_experts)
    file = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "optimizer": {}, "kv_lora_rank": a.kv_lora_rank,
            "qk_nope_head_dim": a.qk_nope_head_dim,
            "qk_rope_head_dim": a.qk_rope_head_dim,
            "v_head_dim": a.v_head_dim, "experts_routed_over": m.n_experts,
            "experts_held_first": held[0], "n_routed_experts": held[1],
            "num_experts_per_tok": m.top_k,
            "routed_scaling_factor": m.routed_scaling,
            "bias_update_rate": m.bias_rate}
    return MoonlightMoE(file, {"mode": "exact"})


def _x(key, s, d=128, b=2):
    return jax.random.normal(jax.random.PRNGKey(key), (b, s, d)) * 0.5


@pytest.mark.parametrize("path", ["dense", "tiled", "pallas"])
def test_mla_matches_reference_on_every_path(path, monkeypatch):
    """Latent attention at smoke widths (q.k 48, v 32), on the dense,
    the jnp tiled and the interpreted Pallas path, against the
    reference's."""
    cfg = _cfg()
    s = 256
    if path != "dense":
        monkeypatch.setattr(L, "FLASH_THRESHOLD", 1)
    if path == "tiled":
        monkeypatch.setattr(L.ops, "flash_attention_fits", lambda s: False)
    assert L.attention_path(cfg, s, s, decode=False, cross=False,
                            default_positions=True) == path
    p = L.init_attention(jax.random.PRNGKey(1), cfg)
    x = _x(2, s)
    with jax.default_matmul_precision("highest"):
        got, cache = L.attention(p, cfg, x)
        want = jax.vmap(lambda r: _ref(cfg)._attn(r, p))(x)
    assert cache is None and got.shape == (2, s, cfg.d_model)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_mla_runs_under_its_scopes():
    cfg = _cfg()
    p = L.init_attention(jax.random.PRNGKey(1), cfg)
    hlo = jax.jit(lambda p_, x: L.attention(p_, cfg, x)[0]).lower(
        p, _x(2, 64)).as_text(dialect="hlo", debug_info=True)
    for scope in ("attention/latent/", "attention/core/"):
        assert scope in hlo, scope


def test_mla_refuses_a_cache():
    cfg = _cfg()
    with pytest.raises(ValueError, match="latent cache"):
        M.init_caches(cfg, 1, 16)


def test_leading_dense_layers_refuse_a_cache():
    """Leading dense layers run with no KV cache, and the serving shapes
    are no runnable shape of a latent-attention model."""
    cfg = _cfg()
    p = M.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="no KV cache"):
        M._apply_stack(p["decoder"], cfg, cfg.n_layers - 1, _x(2, 16),
                       positions=None, caches={"groups": [], "tail": []})
    assert C.runnable_shapes(C.get(SMOKE)) == ("train_4k",)
    assert "decode_32k" in C.runnable_shapes(C.get("granite-moe-3b-a800m"))


def test_router_bias_refuses_padded_experts():
    """A dummy padded expert's count is 0, so its bias would rise by
    bias_rate every step until it passes every sigmoid score: a router
    bias needs unpadded experts."""
    with pytest.raises(ValueError, match="unpadded experts"):
        MOE.init_moe(jax.random.PRNGKey(0),
                     _cfg(n_experts=12, expert_pad_multiple=16))
    assert "bias" in MOE.init_moe(jax.random.PRNGKey(0), _cfg())


def test_sigmoid_router_bias_chooses_but_is_no_gate():
    """The top k of sigmoid(logits) + bias; gates the chosen sigmoid
    scores over their sum times the scaling, whatever the bias."""
    cfg = _cfg()
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    x = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.d_model))
    router = jax.random.normal(jax.random.PRNGKey(4), (cfg.d_model, e)) * .1
    s = jax.nn.sigmoid(x @ router)
    bias = jnp.zeros((e,)).at[0].set(10.0)   # expert 0 always chosen
    for b in (jnp.zeros((e,)), bias):
        gates, ids, aux, share = MOE._route({"router": router, "bias": b},
                                            cfg, x, e)
        want_ids = jax.lax.top_k(s + b, k)[1]
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
        chosen = jnp.take_along_axis(s, ids, -1)
        np.testing.assert_allclose(
            np.asarray(gates),
            np.asarray(chosen / chosen.sum(-1, keepdims=True) * 2.446),
            rtol=1e-6)
        np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.446,
                                   rtol=1e-6)
        assert float(aux) == 0.0
        assert float(share.sum()) == pytest.approx(1.0)
    assert bool((ids == 0).any(-1).all())   # the bias moved the choice


def _moe_layer(cfg, key=5):
    p = MOE.init_moe(jax.random.PRNGKey(key), cfg)
    p["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(key + 1),
                                         p["bias"].shape)
    return p


def test_held_shares_add_up_to_the_uncut_layer():
    """Each of 4 shares of 4 experts computes its own experts' part; the
    4 parts plus the shared experts counted once equal the layer with
    all 16 experts held, and each share's part equals the reference's
    (every held expert on every token, masked)."""
    whole = _cfg()
    p = _moe_layer(whole)
    x = _x(7, 32)
    shared = jax.vmap(lambda r: _ref(whole)._swiglu(r, p["shared"]))(x)
    with jax.default_matmul_precision("highest"):
        full = MOE.moe_block(p, whole, x)[0]
        total = shared
        for first in range(0, 16, 4):
            cut = _cfg(n_held=4, held_first=first)
            pc = {**p, **{w: p[w][first:first + 4]
                          for w in ("wi", "wg", "wo")}}
            part, _, stats = MOE.moe_block(pc, cut, x)
            want = jax.vmap(lambda r: _ref(cut)._moe(r, pc, pc["bias"])[0])(x)
            np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                       atol=2e-5, rtol=2e-4)
            total = total + (part - shared)
            assert float(stats["moe_dropped"]) == 0.0
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               atol=2e-5, rtol=2e-4)


def test_held_share_counts_and_gradients():
    """The held rows are the picks of held experts; the share's
    gradients equal the reference's (absent experts' rows get none)."""
    cut = _cfg(n_held=4, held_first=8)
    p = _moe_layer(cut, key=11)
    p = {**p, **{w: p[w][:4] for w in ("wi", "wg", "wo")}}
    x = _x(8, 32)
    _, ids, _, _ = MOE._route(p, cut, x.reshape(-1, cut.d_model), 16)
    held_rows = int(((ids >= 8) & (ids < 12)).sum())
    weights = {k: v for k, v in p.items() if k != "bias"}

    def prog(w, x_):
        y, _, st = MOE.moe_block({**w, "bias": p["bias"]}, cut, x_)
        return jnp.sum(y * y), st

    def ref(w, x_):
        y = jax.vmap(lambda r: _ref(cut)._moe(r, {**w}, p["bias"])[0])(x_)
        return jnp.sum(y * y)

    with jax.default_matmul_precision("highest"):
        (_, st), g = jax.value_and_grad(prog, (0, 1), has_aux=True)(
            weights, x)
        gw = jax.grad(ref, (0, 1))(weights, x)
    n_moe = cut.n_layers - cut.n_dense_layers
    assert float(st["moe_held_rows"]) * n_moe == held_rows
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gw)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale, rtol=1e-3)


def _held_layer(first, to_held=False):
    """A layer holding 4 of the 16 experts from ``first`` (float32); with
    ``to_held`` its router bias sends every pick to them."""
    cut = _cfg(n_held=4, held_first=first)
    p = _moe_layer(cut, key=21 + first)
    if to_held:
        ids = jnp.arange(cut.moe.n_experts)
        p["bias"] = jnp.where((ids >= first) & (ids < first + 4), 10.0, 0.)
    return cut, p


def _block(cut, p, x, monkeypatch, full=False):
    """The held block's output, counters and gradients of its weights
    (router, experts, shared experts) and of x; ``full``: in a buffer
    of all G*k routed rows, with no fallback."""
    if full:
        monkeypatch.setattr(MOE, "_buffer_rows", lambda cfg, routed: routed)
    weights = {k: v for k, v in p.items() if k != "bias"}

    def f(w, x_):
        y, _, st = MOE.moe_block({**w, "bias": p["bias"]}, cut, x_)
        return jnp.sum(y * y), (y, st)

    with jax.default_matmul_precision("highest"):
        (_, (y, st)), g = jax.jit(jax.value_and_grad(
            f, (0, 1), has_aux=True))(weights, x)
    monkeypatch.undo()
    return y, st, g


def _assert_same_block(got, want):
    (y, st, g), (y_w, st_w, g_w) = got, want
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_w))
    np.testing.assert_array_equal(np.asarray(st["moe_counts"]),
                                  np.asarray(st_w["moe_counts"]))
    assert float(st["moe_held_rows"]) == float(st_w["moe_held_rows"])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree.leaves(g_w)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6,
            atol=1e-6 * float(jnp.max(jnp.abs(b))),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("first", [0, 4, 12])
def test_compact_buffer_matches_the_full_size_one(first, monkeypatch):
    """4 of 16 experts held over G*k = 256*4 routed rows: the held rows
    (about 256) run in a compact buffer of C = 512, and the block's
    output, routed counts and gradients (of x, the router, the experts
    and the shared experts) equal those of the full-size buffer; the
    output equals the reference's; no layer overflowed."""
    cut, p = _held_layer(first)
    x = _x(30 + first, 128)
    assert MOE._buffer_rows(cut, 256 * 4) == 512
    compact = _block(cut, p, x, monkeypatch)
    _assert_same_block(compact, _block(cut, p, x, monkeypatch, full=True))
    n_moe = cut.n_layers - cut.n_dense_layers
    assert 0 < float(compact[1]["moe_held_rows"]) * n_moe <= 512
    assert float(compact[1]["moe_held_overflow"]) == 0.0
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda r: _ref(cut)._moe(r, p, p["bias"])[0])(x)
    np.testing.assert_allclose(np.asarray(compact[0]), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("first,s", [(0, 128), (12, 128), (4, 120)])
def test_overflowing_held_rows_run_in_token_chunks(first, s, monkeypatch):
    """A router bias that sends every pick to the 4 held experts gives
    G*k = 2*s*4 held rows, more than the compact buffer's 512: the block
    runs the tokens in chunks whose picks fit it (two of 128 tokens at
    s = 128, two of 120 at s = 120), counts that in
    ``moe_held_overflow`` and equals the full-size buffer's, gradients
    included."""
    cut, p = _held_layer(first, to_held=True)
    x = _x(40 + first, s)
    assert MOE._buffer_rows(cut, 2 * s * 4) == 512
    got = _block(cut, p, x, monkeypatch)
    _assert_same_block(got, _block(cut, p, x, monkeypatch, full=True))
    n_moe = cut.n_layers - cut.n_dense_layers
    assert float(got[1]["moe_held_rows"]) * n_moe == 2 * s * 4
    assert float(got[1]["moe_held_overflow"]) == 1.0


@pytest.mark.parametrize("to_held", [False, True])
def test_train_step_counts_held_overflow(to_held):
    """The train step's ``moe_held_overflow`` is the MoE layer calls
    whose held rows overflowed the compact buffer: 0 on ordinary
    routing, every MoE layer where the router biases send every pick to
    the held experts; the step's loss is finite either way."""
    cut = _cfg(n_held=4, held_first=4)
    step = ts.make_train_step(cut, None, adamw.OptConfig(warmup_steps=1),
                              ts.CelerisConfig(), donate=False)
    state = ts.init_state(jax.random.PRNGKey(0), cut)
    if to_held:
        moe = state["params"]["decoder"]["groups"][0]["moe"]
        ids = jnp.arange(cut.moe.n_experts)
        moe["bias"] = jnp.broadcast_to(
            jnp.where((ids >= 4) & (ids < 8), 10.0, 0.0), moe["bias"].shape)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 128),
                                          0, 512)}
    batch["labels"] = batch["tokens"]
    _, metrics = step(state, batch, jax.random.PRNGKey(2), jnp.float32(0.))
    n_moe = cut.n_layers - cut.n_dense_layers
    assert float(metrics["moe_held_overflow"]) == (n_moe if to_held else 0)
    held_rows = float(metrics["moe_held_rows"])   # a layer's, on average
    assert held_rows == 256 * 4 if to_held else held_rows <= 512
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("share", [None, 4])
def test_block_compiled_alone(share):
    """``moe_block`` forward and backward, compiled alone: its permutes
    gather, forward and backward, and never scatter; a conditional of the
    block's own (``moe/cond``, not the interpreted Pallas kernels' on
    the CPU) only with a held share whose compact buffer
    is cut below G*k, none without one (Granite's block)."""
    if share is None:
        cfg = C.get_smoke("granite-moe-3b-a800m")
        p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    else:
        cfg, p = _held_layer(0)
    x = _x(50, 128, d=cfg.d_model)
    hlo = jax.jit(jax.grad(lambda p_, x_: jnp.sum(jnp.square(
        MOE.moe_block(p_, cfg, x_)[0])), (0, 1))).lower(p, x).compile(
        ).as_text()
    for c in ("dispatch", "combine"):
        mine = [ln for ln in hlo.splitlines() if f"/{c}/" in ln]
        assert not [ln for ln in mine if " scatter(" in ln], c
        for way in ("jvp(", "transpose("):
            assert [ln for ln in mine if " gather(" in ln
                    and f"/{way}" in ln], (c, way)
    conds = [ln for ln in hlo.splitlines() if " conditional(" in ln
             and re.search(r'moe\)*/cond"', ln)]
    assert bool(conds) == (share is not None), conds


def test_bias_update_over_two_steps():
    """After each step every MoE layer's bias moves by bias_rate *
    sign(mean(c) - c), c the step's routed counts at the step's weights
    and bias; the bias is no leaf of AdamW's state."""
    cfg = _cfg()
    step = ts.make_train_step(cfg, None, adamw.OptConfig(warmup_steps=1),
                              ts.CelerisConfig(), donate=False)
    state = ts.init_state(jax.random.PRNGKey(0), cfg)
    assert state["opt"]["mu"]["decoder"]["groups"][0]["moe"]["bias"] is None
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, 512)}
    batch["labels"] = batch["tokens"]
    ref = _ref(cfg)
    for t in range(2):
        params = state["params"]
        counts = M.lm_loss(params, cfg, batch)[1][2]["moe_counts"]
        bias = params["decoder"]["groups"][0]["moe"]["bias"]
        want = ref.update_bias(bias, counts)
        state, metrics = step(state, batch, jax.random.fold_in(key, t),
                              jnp.float32(0.0))
        got = state["params"]["decoder"]["groups"][0]["moe"]["bias"]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert float(metrics["moe_bias_absmax"]) == pytest.approx(
            float(jnp.max(jnp.abs(want))))
        assert float(counts.sum()) == (cfg.n_layers - 1) * 64 * 4
    assert float(jnp.max(jnp.abs(got))) == pytest.approx(2 * 0.001)


def test_leading_dense_layer():
    """Layer 0 is attention then a d_ff-wide dense MLP, before the MoE
    layers; it is a whole layer of the published count."""
    cfg = _cfg()
    p = M.init_params(jax.random.PRNGKey(0), cfg)
    lead = p["decoder"]["lead"]
    assert len(lead) == 1 and "moe" not in lead[0]
    assert lead[0]["mlp"]["wi"].shape == (cfg.d_model, cfg.d_ff)
    assert p["decoder"]["groups"][0]["moe"]["wi"].shape[0] == \
        cfg.n_layers - 1
    # the dense layer runs first: changing its MLP moves the output
    batch = {"tokens": jnp.arange(32, dtype=jnp.int32)[None]}
    out = M.forward(p, cfg, batch)[0]
    p2 = jax.tree.map(lambda a: a, p)
    p2["decoder"]["lead"][0]["mlp"]["wo"] = lead[0]["mlp"]["wo"] * 2
    assert not np.allclose(np.asarray(M.forward(p2, cfg, batch)[0]),
                           np.asarray(out))


def test_coded_trainer_steps():
    """Two steps of the coded train step through ``Trainer``: the loss
    falls, the counters are read with it, the biases moved."""
    from repro.data.pipeline import DataConfig
    from repro.train.trainer import Trainer
    cfg = dataclasses.replace(_cfg(n_held=4, held_first=0),
                              dtype="bfloat16")
    tr = Trainer(cfg, data_cfg=DataConfig(vocab_size=512, seq_len=32,
                                          global_batch=4, seed=1,
                                          kind="uniform"),
                 opt_cfg=adamw.OptConfig(lr=1e-3, warmup_steps=1),
                 celeris=ts.CelerisConfig(mode="lossy_hadamard", n_rot=256,
                                          min_coded_size=1024), seed=1)
    seen = []
    tr.run(3, on_metrics=lambda s, m: seen.append(m))
    assert seen[-1]["loss"] < seen[0]["loss"]
    for m in seen:
        assert m["moe_dropped"] == 0.0 and m["moe_held_rows"] > 0
        assert 0.0 < m["recv_frac"] <= 1.0
    assert seen[-1]["moe_bias_absmax"] == pytest.approx(0.003)


def test_parameter_counts():
    """Moonlight's published total (about 16 B) and one chip's share of
    the 8-way expert-parallel deployment at 1 dense + 5 MoE layers (8
    experts, a 20480-id slice of the vocabulary): 669 M."""
    cfg = C.get(SMOKE)
    assert cfg.param_count() == pytest.approx(15.96e9, rel=1e-3)
    chip = dataclasses.replace(
        cfg, n_layers=6, vocab_size=20480,
        moe=dataclasses.replace(cfg.moe, n_held=8))
    want = (6 * 13_763_072 + 3 * 2048 * 11264
            + 5 * (8 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64)
            + 2 * 20480 * 2048)
    assert chip.param_count() == want
    assert want == pytest.approx(669e6, rel=1e-3)
    state = jax.eval_shape(lambda k: M.init_params(k, chip),
                           jax.random.PRNGKey(0))
    norms = sum(a.size for path, a in
                jax.tree_util.tree_flatten_with_path(state)[0]
                if jax.tree_util.keystr(path).endswith("['scale']")
                and "kv_norm" not in jax.tree_util.keystr(path))
    biases = 5 * 64
    assert sum(a.size for a in jax.tree.leaves(state)) == \
        want + norms + biases
    with open(os.path.join(REPO, "bench/configs/moonlight-16b-a3b.json")) \
            as f:
        assert json.load(f)["reduced"]["num_hidden_layers"]["published"] \
            == cfg.n_layers
