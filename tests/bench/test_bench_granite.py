"""The Granite-3.0 MoE cell at smoke widths on the CPU: the program
against its plain reference, the cell's CPU rehearsal through the
harness's own functions (with the look for a chip skipped), the
planted faults and the float8 control, the MoE scope's reduction and
the work counts of ``bench/roofline_moe.py``."""
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

from bench import checks, control_moe, harness, moe_scope  # noqa: E402
from bench import roofline_moe  # noqa: E402
from bench import run as R  # noqa: E402

CELL = "granite-smoke.coded"
# the registry's smoke preset (2 layers, d_model 128, 8 experts top-2,
# vocab 512) with the Granite multipliers at head_dim 32
SMOKE_CONFIG = {
    "registry": "granite-moe-3b-a800m:smoke", "num_hidden_layers": 2,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 64, "num_local_experts": 8,
    "num_experts_per_tok": 2, "vocab_size": 512, "rope_theta": 10000.0,
    "attention_multiplier": 1 / 32,
    "reduced": {"num_hidden_layers": {"published": 2, "here": 2}},
}
SMOKE_TRAFFIC = {"seq_len": 32, "global_batch": 4, "trace_seconds": 1}
# Sound runs on the CPU (6 seeds) read loss_gap <= 5.4e-6,
# grad_norm_gap <= 0.0037, update_norm_gap <= 4.2e-4 at these widths;
# the float8 control (4 seeds) reads 1.6e-5 to 5.3e-5, 0.010 to 0.020
# and 0.0021 to 0.0031; half a batch 8.8e-4 to 1.2e-3, 0.41 to 0.50 and
# 0.021 to 0.028.
SMOKE_LIMITS = {"loss_gap": 1.2e-5, "grad_norm_gap": 0.007,
                "update_norm_gap": 0.001}


def _smoke_cfg():
    cfg = json.load(open(os.path.join(
        REPO, "bench/configs/granite-moe-3b-a800m.json")))
    cfg.update(SMOKE_CONFIG, name="granite-smoke")
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("granite"))
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(tmp, "bench")
    with open(os.path.join(bench, "configs/granite-smoke.json"), "w") as f:
        json.dump(_smoke_cfg(), f)
    t = json.load(open(os.path.join(bench, "traffic/coded-2k.json")))
    t.update(SMOKE_TRAFFIC)
    with open(os.path.join(bench, "traffic/smoke-coded-2k.json"), "w") as f:
        json.dump(t, f)
    with open(os.path.join(bench, f"limits/{CELL}.json"), "w") as f:
        json.dump(SMOKE_LIMITS, f)
    man = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    man["configs"] = [{"name": "granite-smoke", "source": "smoke",
                       "file": "bench/configs/granite-smoke.json",
                       "reduced": [], "why": "smoke"}]
    man["workloads"] = [{"name": CELL, "config": "granite-smoke",
                         "traffic": "smoke-coded-2k", "chips": 1,
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
    """The program's loss and every gradient leaf, in float32, against
    the reference on the same seeded random weights and batch."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from bench import generator
    from bench.reference.granite_moe import GraniteMoE
    from bench.runners.train_moe import model_config
    from repro.models import model as M

    cfg = _smoke_cfg()
    mc = dataclasses.replace(model_config(cfg), dtype="float32")
    shapes = jax.eval_shape(lambda k: M.init_params(k, mc),
                            jax.random.PRNGKey(0))
    make, key = generator.weight_init(shapes, 5, 0.02)
    params = make(key)
    batch = generator.TokenBatches({"seq_len": 32, "global_batch": 4},
                                   512, 5).global_batch(0)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        (loss, (_, aux, stats)), g = jax.value_and_grad(
            M.lm_loss, has_aux=True)(params, mc, batch, remat=False)
        ref = GraniteMoE(cfg, {"mode": "exact"})
        want_loss, want_g = ref._rows_grads(params, batch["tokens"],
                                            batch["labels"])
    assert float(aux) > 0 and float(stats["moe_dropped"]) == 0.0
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(want_g)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale, rtol=1e-3,
                                   err_msg=jax.tree_util.keystr(path))


def test_granite_cell_is_correct(root):
    line = _line(root, 0)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s",
                                    "train_step_p90_ms", "setup_s"}


def test_granite_cell_traced(root):
    """With --trace 1 the line carries per-layer metrics only; the CPU
    writes no device plane, so the trace-read ones stay silent."""
    line = _line(root, 1)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"trainer_host_ms"}


def _broken(kind):
    """make_train_step with the step broken underneath."""
    import jax
    import jax.numpy as jnp

    from repro.train import train_step as ts
    real_make = ts.make_train_step

    def make(*a, **k):
        real = real_make(*a, **k)

        def step(state, batch, key, drop):
            if kind == "frozen":         # returns its state unchanged
                _, m = real(jax.tree.map(jnp.copy, state), batch, key, drop)
                return state, m
            half = {n: v[: v.shape[0] // 2] for n, v in batch.items()}
            return real(state, half, key, drop)
        return step
    return make


@pytest.mark.parametrize("kind", ["frozen", "half_batch"])
def test_broken_granite_step_is_not_correct(root, kind, monkeypatch):
    from repro.train import train_step as ts
    monkeypatch.setattr(ts, "make_train_step", _broken(kind))
    assert _line(root, 0)["correct"] is False


def test_granite_control_fails_the_limits(root):
    """The reference in float8 in the program's place, and half a batch
    left out, each fail at least one of the cell's limits."""
    man = harness.manifest(root)
    wl = harness.workload(man, CELL)
    cfg = harness.config_file(man, wl["config"], root)
    traffic = harness.traffic_file(wl["traffic"], os.path.join(root,
                                                               "bench"))
    limits = harness.limits_file(CELL, os.path.join(root, "bench"))
    got = control_moe.readings(cfg, traffic, 3, control_moe.param_shapes(cfg))
    for fault, (numbers, _, _) in got.items():
        ok, chk = checks.judge(numbers, limits)
        assert not ok, (fault, chk)


def test_program_routing_flips_are_counted(root):
    """The program's routing decisions over the checked steps against
    the float32 reference's: every decision counted, and few flipped
    (bf16 near-ties)."""
    man = harness.manifest(root)
    wl = harness.workload(man, CELL)
    cfg = harness.config_file(man, wl["config"], root)
    traffic = harness.traffic_file(wl["traffic"], os.path.join(root,
                                                               "bench"))
    got = control_moe.program_flips(cfg, traffic, 7)
    n = (int(traffic["checked_steps"]) * cfg["num_hidden_layers"]
         * traffic["global_batch"] * traffic["seq_len"]
         * cfg["num_experts_per_tok"])
    assert got["decisions"] == n
    assert 0 <= got["flips"] <= 0.05 * n


def test_registry_mismatch_is_refused():
    from bench.runners.train_moe import model_config
    cfg = _smoke_cfg()
    cfg["residual_multiplier"] = 0.5
    with pytest.raises(ValueError, match="residual_multiplier"):
        model_config(cfg)


def test_granite_flops_by_hand():
    cfg = json.load(open(os.path.join(
        REPO, "bench/configs/granite-moe-3b-a800m.json")))
    # per layer: wq and wo 1536x1536, wk and wv 1536x512 (8 kv heads of
    # 64), the router 1536x40, 8 routed experts of three 1536x512
    # matrices; plus the tied LM head 49155x1536 once
    attn = 1536 * 1536 * 2 + 1536 * 512 * 2
    per_layer = attn + 1536 * 40 + 8 * 3 * 1536 * 512
    assert (attn, per_layer) == (6_291_456, 25_227_264)
    params = 6 * per_layer + 49155 * 1536
    assert roofline_moe.moe_lm_matmul_params(cfg) == params
    tokens = 2048 * 2
    want = tokens * (6 * params + 12 * 6 * 2048 * 1536)
    assert want == pytest.approx(6.50e12, rel=2e-3)
    assert roofline_moe.moe_lm_train_flops(cfg, 2048, tokens) == want
    # the expert matmuls: 4096 x 8 routed rows through three 1536x512
    # matrices, forward and backward, 6 layers; the checkpointed step
    # runs the forward a second time
    work = roofline_moe.experts_work(cfg, tokens, recompute=False)
    assert work["flops"] == 3 * 6 * 2 * 32768 * 1536 * 512 * 3
    assert work["flops"] / 197e12 == pytest.approx(14.1e-3, rel=2e-3)
    rows = 2 * 32768 * 1536 * 2
    weights = 3 * 40 * 1536 * 512 * 2
    assert work["bytes"] == 3 * 6 * (weights + rows)
    remat = roofline_moe.experts_work(cfg, tokens, recompute=True)
    assert remat["flops"] == 4 * 6 * 2 * 32768 * 1536 * 512 * 3
    assert remat["bytes"] == 4 * 6 * (weights + rows)


def test_moe_scope_reduction():
    """Self time under ``moe`` and its children, over the window: a
    while op and the ops it holds count once, by the innermost op."""
    ms = 1_000_000
    ops = [("while", 0, 10 * ms, "jit(step)/fwd_bwd/while"),
           ("f1", 1 * ms, 3 * ms, "jit(step)/fwd_bwd/moe/route/dot"),
           ("f2", 3 * ms, 7 * ms,
            "jit(step)/fwd_bwd/transpose(jvp(moe))/moe/experts/ragged"),
           ("f3", 7 * ms, 8 * ms, "jit(step)/fwd_bwd/moe/reshape"),
           ("f4", 12 * ms, 13 * ms, "jit(step)/optimizer/mul")]
    spans = [("bench.window", 0, 20 * ms, None),
             ("trainer.step", 0, 20 * ms, 0)]
    red = moe_scope.reduce({"devices": {0: ops}, "spans": spans}, [0])
    assert red["steps"] == 1
    assert red["moe_s"] == pytest.approx(7e-3)
    assert red["children_s"] == pytest.approx(
        {"route": 2e-3, "dispatch": 0.0, "experts": 4e-3, "combine": 0.0})
    assert moe_scope.reduce({"devices": {}, "spans": spans}, [0]) is None
