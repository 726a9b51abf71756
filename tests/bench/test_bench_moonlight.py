"""The Moonlight-16B-A3B cell at smoke widths on the CPU: the program
against its plain reference, the cell's CPU rehearsal through the
harness's own functions (with the look for a chip skipped), the planted
faults and the float8 control, the latent-attention scope's reduction
and the work counts of ``bench/roofline_mla_moe.py``."""
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(REPO, "src"), REPO, HERE]

from bench import checks, control_mla_moe, harness, mla_scope  # noqa: E402
from bench import roofline_mla_moe  # noqa: E402
from bench import run as R  # noqa: E402

CELL = "moonlight-smoke.coded"
# the registry's smoke preset (1 dense + 2 MoE layers, d_model 128, 4
# heads of latent attention, 16 routed experts top-4 of which experts
# 4-7 are held, 2 shared, vocab 512)
SMOKE_CONFIG = {
    "registry": "moonlight-16b-a3b:smoke", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
    "v_head_dim": 32, "n_routed_experts": 4, "experts_routed_over": 16,
    "experts_held_first": 4, "num_experts_per_tok": 4,
    "n_shared_experts": 2, "vocab_size": 512,
    "reduced": {"num_hidden_layers": {"published": 3, "here": 3},
                "n_routed_experts": {"published": 16, "here": 4},
                "vocab_size": {"published": 512, "here": 512}},
    "program": {"held_first": 4},
}
SMOKE_TRAFFIC = {"seq_len": 32, "global_batch": 4, "trace_seconds": 1}
# Sound runs on the CPU (6 seeds) read loss_gap <= 8.1e-5,
# grad_norm_gap <= 0.0021, update_norm_gap <= 0.0019 at these widths;
# the float8 control (3 seeds) reads 1.4e-4 to 5.7e-4, 0.012 to 0.022
# and 0.0030 to 0.0035; half a batch 2.7e-3 to 6.5e-3, 0.041 to 0.073
# and 0.009 to 0.018.
# Of the 32 router-bias entries, sound runs (7 seeds) end the checked
# steps apart from the reference on 0 to 4; the control on 4 to 10,
# half a batch on 13 to 21; a bias left unchanged or moved the wrong way
# on about all.
SMOKE_LIMITS = {"loss_gap": 1.2e-4, "grad_norm_gap": 0.006,
                "update_norm_gap": 0.0025, "router_bias_differing": 0.25}


def _smoke_cfg():
    cfg = json.load(open(os.path.join(
        REPO, "bench/configs/moonlight-16b-a3b.json")))
    cfg.update(SMOKE_CONFIG, name="moonlight-smoke")
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("moonlight"))
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(tmp, "bench")
    with open(os.path.join(bench, "configs/moonlight-smoke.json"), "w") as f:
        json.dump(_smoke_cfg(), f)
    t = json.load(open(os.path.join(bench, "traffic/coded-8k.json")))
    t.update(SMOKE_TRAFFIC)
    with open(os.path.join(bench, "traffic/smoke-coded-8k.json"), "w") as f:
        json.dump(t, f)
    with open(os.path.join(bench, f"limits/{CELL}.json"), "w") as f:
        json.dump(SMOKE_LIMITS, f)
    man = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    man["configs"] = [{"name": "moonlight-smoke", "source": "smoke",
                       "file": "bench/configs/moonlight-smoke.json",
                       "reduced": [], "why": "smoke"}]
    man["workloads"] = [{"name": CELL, "config": "moonlight-smoke",
                         "traffic": "smoke-coded-8k", "chips": 1,
                         "why": "smoke"}]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return tmp


def _line(root, trace, seed="2147483713"):
    args = R.parse(["--workload", CELL, "--seed", seed, "--seconds", "1",
                    "--trace", str(trace)])
    out = R.run(args, require_tpu=False, t_start=time.perf_counter(),
                root=root)
    return json.loads(harness.result_line(**out))


def test_program_matches_reference_at_float32():
    """The program's loss, routed counts and every gradient leaf, in
    float32, against the reference on the same seeded random weights
    (a nonzero router bias among them) and batch."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from bench import generator
    from bench.reference.moonlight import MoonlightMoE
    from bench.runners.train_mla_moe import model_config
    from repro.models import model as M
    from repro.models import moe as MOE

    cfg = _smoke_cfg()
    mc = dataclasses.replace(model_config(cfg), dtype="float32")
    shapes = jax.eval_shape(lambda k: M.init_params(k, mc),
                            jax.random.PRNGKey(0))
    make, key = generator.weight_init(shapes, 5, 0.02)
    params = make(key)
    group = params["decoder"]["groups"][0]
    group["moe"]["bias"] = 0.01 * jax.random.normal(
        jax.random.PRNGKey(9), group["moe"]["bias"].shape)
    weights, biases = MOE.split_bias(params)
    batch = generator.TokenBatches({"seq_len": 32, "global_batch": 4},
                                   512, 5).global_batch(0)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    def loss_fn(w):
        return M.lm_loss(MOE.join_bias(w, biases), mc, batch, remat=False)

    with jax.default_matmul_precision("highest"):
        (loss, (_, aux, stats)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(weights)
        ref = MoonlightMoE(cfg, {"mode": "exact"})
        from bench.reference.moonlight import split_bias
        rw, rb = split_bias(params)
        want_loss, want_g, want_counts = ref._rows_grads(
            rw, rb, batch["tokens"], batch["labels"])
    assert float(aux) == 0.0 and float(stats["moe_dropped"]) == 0.0
    np.testing.assert_array_equal(np.asarray(stats["moe_counts"]),
                                  np.asarray(want_counts))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(want_g)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale, rtol=1e-3,
                                   err_msg=jax.tree_util.keystr(path))


def test_moonlight_cell_is_correct(root):
    line = _line(root, 0)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s",
                                    "train_step_p90_ms", "setup_s"}


def test_moonlight_cell_traced(root):
    """With --trace 1 the line carries per-layer metrics only; the CPU
    writes no device plane, so the trace-read ones stay silent."""
    line = _line(root, 1)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"trainer_host_ms"}


def _broken(kind):
    """make_train_step with the step broken underneath."""
    import jax
    import jax.numpy as jnp

    from repro.models import moe as MOE
    from repro.train import train_step as ts
    real_make = ts.make_train_step

    def make(*a, **k):
        real = real_make(*a, **k)

        def step(state, batch, key, drop):
            if kind == "frozen":         # returns its state unchanged
                _, m = real(jax.tree.map(jnp.copy, state), batch, key, drop)
                return state, m
            if kind in ("bias_frozen", "bias_flipped"):
                old = jax.tree.map(jnp.copy, MOE.split_bias(
                    state["params"])[1])
                new, m = real(state, batch, key, drop)
                weights, moved = MOE.split_bias(new["params"])
                if kind == "bias_flipped":   # each step the other way
                    old = jax.tree.map(lambda o, n: 2 * o - n, old, moved)
                return {**new, "params": MOE.join_bias(weights, old)}, m
            half = {n: v[: v.shape[0] // 2] for n, v in batch.items()}
            return real(state, half, key, drop)
        return step
    return make


@pytest.mark.parametrize("kind", ["frozen", "half_batch", "bias_frozen",
                                  "bias_flipped"])
def test_broken_moonlight_step_is_not_correct(root, kind, monkeypatch):
    """A step broken underneath is not correct; a router bias left
    unchanged, or moved the wrong way, fails on the bias alone."""
    from repro.train import train_step as ts
    monkeypatch.setattr(ts, "make_train_step", _broken(kind))
    line = _line(root, 0)
    assert line["correct"] is False
    if kind.startswith("bias"):
        chk = line["checks"]["router_bias_differing"]
        assert chk["value"] > 0.9, chk


def test_moonlight_control_fails_the_limits(root):
    """The reference in float8 in the program's place, and half a batch
    left out, each fail at least one of the cell's limits."""
    man = harness.manifest(root)
    wl = harness.workload(man, CELL)
    cfg = harness.config_file(man, wl["config"], root)
    traffic = harness.traffic_file(wl["traffic"], os.path.join(root,
                                                               "bench"))
    limits = harness.limits_file(CELL, os.path.join(root, "bench"))
    got = control_mla_moe.readings(cfg, traffic, 3,
                                   control_mla_moe.param_shapes(cfg))
    assert set(got) == {"control", "half_batch"}
    for fault, (numbers, _) in got.items():
        ok, chk = checks.judge(numbers, limits)
        assert not ok, (fault, chk)


def test_registry_mismatch_is_refused():
    from bench.runners.train_mla_moe import model_config
    for key, value in (("kv_lora_rank", 32), ("routed_scaling_factor", 1.0),
                       ("experts_routed_over", 32), ("topk_method", "greedy")):
        cfg = _smoke_cfg()
        cfg[key] = value
        with pytest.raises(ValueError, match="differs"):
            model_config(cfg)


def test_moonlight_flops_by_hand():
    cfg = json.load(open(os.path.join(
        REPO, "bench/configs/moonlight-16b-a3b.json")))
    # latent attention: wq 2048 x 16*192, wkv_a 2048 x (512+64), wkv_b
    # 512 x 16*(128+128), wo 16*128 x 2048
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attn == 13_762_560 == roofline_mla_moe.attention_params(cfg)
    # 6 layers of attention, the dense layer's 3 x 2048 x 11264 MLP, 5
    # routers over 64 experts and shared experts of width 2 x 1408, the
    # head over the 20480-id slice
    dense = (6 * attn + 3 * 2048 * 11264
             + 5 * (2048 * 64 + 3 * 2048 * 2816) + 20480 * 2048)
    assert roofline_mla_moe.dense_matmul_params(cfg) == dense
    tokens, rows = 8192, 6144.0          # rows a MoE layer routed here
    causal = 8192 ** 2 * 16 * (192 + 128)  # a layer's scores and values
    assert causal == pytest.approx(343.6e9, rel=1e-3)
    want = 6 * tokens * dense + 6 * 5 * rows * 3 * 2048 * 1408 \
        + 3 * 6 * causal
    assert want == pytest.approx(21.6e12, rel=5e-3)
    assert roofline_mla_moe.train_flops(cfg, 8192, tokens, rows) == \
        pytest.approx(want)
    # the kernels: per layer twice the forward, dq and dkv
    k = roofline_mla_moe.attention_kernel_work(cfg, 8192, 1)
    tri = 8192 ** 2 * 16
    assert k["flops"] == pytest.approx(
        6 * tri * (2 * 320 + (384 + 128) + (384 + 256)))
    assert k["flops"] / 197e12 == pytest.approx(58.6e-3, rel=2e-3)
    # the held experts: 4 passes over 5 layers of the held rows
    e = roofline_mla_moe.experts_work(cfg, rows, recompute=True)
    assert e["flops"] == 4 * 5 * 2 * rows * 2048 * 1408 * 3
    assert e["bytes"] == 4 * 5 * (3 * 8 * 2048 * 1408 * 2
                                  + 2 * rows * 2048 * 2)


def test_wrapped_kernel_lines_are_joined():
    """A kernel instruction whose backend config wraps over lines gets
    its op_name once the lines are joined."""
    from bench import layers
    hlo = "\n".join([
        "HloModule jit_step, entry_computation_layout={()}",
        "",
        "ENTRY %main (p: bf16[8]) -> bf16[8] {",
        '  %p = bf16[8] parameter(0), metadata={op_name="x"}',
        '  ROOT %splash_mha_fwd.3 = bf16[8] custom-call(%p), '
        'custom_call_target="tpu_custom_call", backend_config={"a":{',
        '"xprof_metadata":"{\\"block_q\\": 512, \\"block_kv\\": 512}"',
        '}}, metadata={op_name="jit(step)/fwd_bwd/attention/core/'
        'splash_mha_fwd/pallas_call"}',
        "}"])
    assert layers.op_names(hlo)[1]["splash_mha_fwd.3"] == ""
    names = layers.op_names(mla_scope.join_wrapped(hlo))[1]
    assert names["splash_mha_fwd.3"].endswith("attention/core/"
                                              "splash_mha_fwd/pallas_call")
    assert names["p"] == "x"


def test_mla_scope_reduction():
    """Self time under ``attention``, its children and its kernels, over
    the window, by the innermost op."""
    ms = 1_000_000
    ops = [("while", 0, 10 * ms, "jit(step)/fwd_bwd/while"),
           ("f1", 1 * ms, 2 * ms, "jit(step)/fwd_bwd/attention/latent/dot"),
           ("k1", 2 * ms, 6 * ms, "jit(step)/fwd_bwd/attention/core/"
            "vmap(jit(_splash_attention))/splash_mha_fwd/pallas_call"),
           ("f2", 6 * ms, 7 * ms, "jit(step)/fwd_bwd/attention/dot"),
           ("f3", 7 * ms, 8 * ms, "jit(step)/fwd_bwd/moe/experts/gmm"),
           ("f4", 12 * ms, 13 * ms, "jit(step)/optimizer/mul")]
    spans = [("bench.window", 0, 20 * ms, None),
             ("trainer.step", 0, 20 * ms, 0)]
    red = mla_scope.reduce({"devices": {0: ops}, "spans": spans}, [0])
    assert red["steps"] == 1
    assert red["attention_s"] == pytest.approx(6e-3)
    assert red["children_s"] == pytest.approx({"latent": 1e-3,
                                               "core": 4e-3})
    assert red["kernel_s"] == pytest.approx(4e-3)
    assert mla_scope.reduce({"devices": {}, "spans": spans}, [0]) is None
