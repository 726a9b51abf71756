"""Train cells of a decoder LM with DeepSeek-V3's block (latent
attention, a leading dense layer, MoE layers routed by sigmoid scores
with a load-balancing bias, shared experts) on one chip's share of an
expert-parallel deployment: the program's ``Trainer`` on
Moonlight-16B-A3B.

As ``bench/runners/train_moe.py`` (set-up drives the window's one
``Trainer`` through the checked steps, the chip's memory is compacted
once, the window calls ``Trainer.run(1)`` back to back, and after it
the program's state is freed and a reference repeats the checked
steps), with:

- every width checked against the program's registry and the
  configuration file; the depth, the experts held and the vocabulary
  are the file's (``reduced``), the registry's the published ones;
- the router biases in the train state's parameters but not in AdamW's
  state (``repro.models.moe.split_bias``), and judged apart from the
  weights' leaves: ``router_bias_differing`` is the share of bias
  entries on which program and reference differ by more than half a
  step of the update after the checked steps (a bias left unchanged,
  or moved the wrong way, reads about 1);
- the reference ``bench/reference/moonlight.py``, and the work counts of
  ``bench/roofline_mla_moe.py``, the held experts by the rows the
  program routed to them (its ``moe_held_rows``, averaged over the
  window's steps);
- with ``--trace 1``, the record keeps the split of the trace by the
  program's layers (``bench/layers.py``), of its ``moe`` scope
  (``bench/moe_scope.py``) and of its latent ``attention`` scope and
  kernels (``bench/mla_scope.py``), each operation named through the
  compiled step's HLO with its wrapped kernel lines joined.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import tempfile
import time

import numpy as np

from bench import checks, generator, harness, layers, mla_scope, moe_scope
from bench import roofline_mla_moe as work
from bench import trace as trace_mod
from bench.runners.train import _Watch

# the configuration file's key for each ModelConfig field it pins
WIDTHS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
          "n_dense_layers": "first_k_dense_replace",
          "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
          "tie_embeddings": "tie_word_embeddings",
          "qkv_bias": "attention_bias"}
MLA_WIDTHS = {"kv_lora_rank": "kv_lora_rank",
              "qk_nope_head_dim": "qk_nope_head_dim",
              "qk_rope_head_dim": "qk_rope_head_dim",
              "v_head_dim": "v_head_dim"}
MOE_WIDTHS = {"n_experts": "experts_routed_over",
              "top_k": "num_experts_per_tok",
              "d_expert": "moe_intermediate_size",
              "n_shared": "n_shared_experts",
              "score_func": "scoring_func",
              "routed_scaling": "routed_scaling_factor",
              "bias_rate": "bias_update_rate",
              "aux_weight": "seq_aux_loss_alpha",
              "n_held": "n_routed_experts",
              "held_first": "experts_held_first"}


def bias_differing(got, want, rate: float) -> float:
    """The share of router-bias entries on which ``got`` and ``want``
    differ by more than half a step ``rate`` of the update."""
    got = np.asarray(got, np.float32).reshape(-1)
    want = np.asarray(want, np.float32).reshape(-1)
    return float(np.mean(np.abs(got - want) > 0.5 * rate))


def model_config(cfg: dict):
    """The program's ModelConfig for this configuration file: the
    registry's entry at the file's depth, vocabulary and held experts,
    refused unless every width equals the file's, the registry's depth,
    vocabulary and experts are the published ones, and the block is
    DeepSeek-V3's (sigmoid routing with noaux_tc's bias over one group,
    gates renormalised over the k, no query latent, one MoE layer per
    layer after the dense ones, no auxiliary loss, a SwiGLU, the
    embedding unscaled)."""
    import repro.configs as C
    name, _, variant = cfg["registry"].partition(":")
    base = C.get_smoke(name) if variant == "smoke" else C.get(name)
    program = dict(cfg.get("program", {}))
    mc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"],
        vocab_size=cfg["vocab_size"],
        moe=dataclasses.replace(base.moe, n_held=cfg["n_routed_experts"],
                                held_first=program.pop("held_first")),
        **program)
    wrong = {f: (getattr(mc, f), cfg[k]) for f, k in WIDTHS.items()
             if getattr(mc, f) != cfg[k]}
    wrong.update({f"mla.{f}": (getattr(mc.mla, f), cfg[k])
                  for f, k in MLA_WIDTHS.items()
                  if getattr(mc.mla, f) != cfg[k]})
    wrong.update({f"moe.{f}": (getattr(mc.moe, f), cfg[k])
                  for f, k in MOE_WIDTHS.items()
                  if getattr(mc.moe, f) != cfg[k]})
    red = cfg["reduced"]
    for f, got in (("n_layers", base.n_layers),
                   ("vocab_size", base.vocab_size),
                   ("moe.n_experts", base.moe.n_experts)):
        want = red[{"n_layers": "num_hidden_layers",
                    "vocab_size": "vocab_size",
                    "moe.n_experts": "n_routed_experts"}[f]]["published"]
        if got != want:
            wrong[f"published {f}"] = (got, want)
    v3 = (cfg["model_type"] == "deepseek_v3" and cfg["q_lora_rank"] is None
          and cfg["topk_method"] == "noaux_tc" and cfg["n_group"] == 1
          and cfg["norm_topk_prob"] is True
          and cfg["topk_group"] == 1 and cfg["moe_layer_freq"] == 1
          and cfg["num_nextn_predict_layers"] == 0
          and cfg["hidden_act"] == "silu" and mc.mlp_type == "swiglu"
          and mc.block_pattern == ("moe",) and mc.moe.router_z_weight == 0
          and mc.embedding_multiplier == 1.0
          and mc.attention_multiplier is None and mc.logits_scaling == 1.0
          and mc.residual_multiplier == 1.0 and mc.head_pad_multiple == 1
          and mc.moe.expert_pad_multiple == 1)
    if not v3:
        wrong["block"] = "not DeepSeek-V3's block as the file states it"
    if wrong:
        raise ValueError(f"registry {cfg['registry']!r} differs from the "
                         f"configuration file: {wrong}")
    return mc


def start(cfg: dict, traffic: dict, seed: int):
    """The program's ``Trainer`` for this configuration and traffic, on
    the benchmark's weights and batches from ``seed``: ``(trainer,
    batches, straggler, make, wkey, shapes)``, with ``make(wkey)`` the
    initial parameters (router biases zero) and ``shapes`` the train
    state's layout."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig
    from repro.models import moe as MOE
    from repro.optim import adamw
    from repro.optim.adamw import OptConfig
    from repro.train import train_step as ts
    from repro.train.trainer import Trainer

    mc = model_config(cfg)
    straggler = (generator.Straggler(traffic["straggler"], seed)
                 if traffic.get("straggler") else None)
    trainer = Trainer(
        mc, data_cfg=DataConfig(vocab_size=mc.vocab_size,
                                seq_len=traffic["seq_len"],
                                global_batch=traffic["global_batch"],
                                seed=seed, kind="uniform"),
        opt_cfg=OptConfig(**cfg["optimizer"]),
        celeris=ts.CelerisConfig(mode=traffic["mode"],
                                 **traffic.get("celeris", {})),
        seed=seed, straggler=straggler)
    # the benchmark's weights and batches replace the Trainer's own
    trainer.state = None
    gc.collect()
    batches = generator.TokenBatches(traffic, mc.vocab_size, seed)
    trainer.source = batches
    shapes = jax.eval_shape(lambda k: ts.init_state(k, mc),
                            jax.random.PRNGKey(0))
    make, wkey = generator.weight_init(shapes["params"], seed,
                                       cfg["initializer_range"])

    def make_state(key):
        params = make(key)
        return {"params": params,
                "opt": adamw.init_opt_state(MOE.split_bias(params)[0]),
                "step": jnp.zeros((), jnp.int32)}

    trainer.state = jax.jit(make_state)(wkey)
    return trainer, batches, straggler, make, wkey, shapes


def run_cell(*, cfg, traffic, limits, seed, seconds, trace, devices,
             t_start) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.models import moe as MOE
    from repro.train import train_step as ts

    from bench.reference.moonlight import MoonlightMoE

    trainer, batches, straggler, make, wkey, shapes = start(cfg, traffic,
                                                            seed)

    # the checked steps, through the window's own call
    n_check = int(traffic["checked_steps"])
    last = {}
    on_metrics = lambda step, m: last.update(m)   # noqa: E731
    prog = {"loss": []}
    for t in range(n_check):
        trainer.run(1, on_metrics=on_metrics)
        prog["loss"].append(last["loss"])
        if t == 0:
            prog["grad_norms"] = checks.leaf_norms(
                trainer.state["opt"]["mu"])
    counters = {k: last[k] for k in ("moe_load_max", "moe_dropped",
                                     "moe_held_rows", "moe_bias_absmax")}
    # each weight's change in one program, which holds no second copy
    # of the weights beside the state
    change = jax.jit(lambda m, k: [
        jnp.sqrt(jnp.sum(jnp.square(a - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(m),
                        jax.tree.leaves(MOE.split_bias(make(k))[0]))])
    prog["change_norms"] = [float(x) for x in change(
        trainer.state["opt"]["master"], wkey)]
    prog_biases = np.concatenate([np.asarray(b).reshape(-1) for b in
                                  jax.tree.leaves(MOE.split_bias(
                                      trainer.state["params"])[1])])
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 MOE.split_bias(shapes["params"])[0])[0]]
    try:   # compact device memory: the window's steps allocate no more
        devices[0].client.defragment()
    except jax.errors.JaxRuntimeError:   # a runtime that cannot (the CPU)
        pass

    # the window
    window_s = min(seconds, traffic["trace_seconds"]) if trace else seconds
    steps = []
    tmp = tempfile.TemporaryDirectory() if trace else None
    if trace:
        jax.profiler.start_trace(tmp.name)
    watch = _Watch()
    t_w0 = time.perf_counter()
    setup_s = t_w0 - t_start
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        while time.perf_counter() - t_w0 < window_s:
            t0 = time.perf_counter()
            with jax.profiler.StepTraceAnnotation("bench.step",
                                                  step_num=len(steps)):
                trainer.run(1, on_metrics=on_metrics)
            steps.append({"s": time.perf_counter() - t0,
                          "call_s": last["wall_s"], "loss": last["loss"],
                          "held_rows": last["moe_held_rows"]})
    t_w1 = time.perf_counter()
    watch.detach()
    if trace:
        jax.profiler.stop_trace()
    device = harness.device_report(devices)
    failed = sum(1 for s in steps if not math.isfinite(s["loss"]))

    seq = traffic["seq_len"]
    tokens = traffic["global_batch"] * seq
    durations = [s["s"] for s in steps]
    held_rows = float(np.mean([s["held_rows"] for s in steps]))
    end_to_end = {
        "train_tokens_per_s": tokens * len(steps) / (t_w1 - t_w0),
        "train_step_p90_ms": float(np.percentile(durations, 90)) * 1e3,
        "setup_s": setup_s,
    }
    record, breakdown = None, None
    if trace:
        path = trace_mod.xplane_path(tmp.name)
        red = trace_mod.reduce(trace_mod.load(path), [d.id for d in devices])
        step = trainer.start_step
        hlo = trainer.step_fn.lower(
            trainer.state, trainer._put_batch(step),
            jax.random.fold_in(trainer.key, step),
            jnp.float32(0.0)).compile().as_text()
        split = layers.load(path, mla_scope.join_wrapped(hlo))
        tmp.cleanup()
        ids = [d.id for d in devices]
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["top_ops"],
                         "idle_gaps": red["idle_gaps"]}
        record = {"kind": "train", "steps": steps, "trace": red,
                  "chips": len(devices),
                  "device_kind": devices[0].device_kind,
                  "flops_per_step": work.train_flops(cfg, seq, tokens,
                                                     held_rows),
                  "experts_work": work.experts_work(cfg, held_rows,
                                                    recompute=True),
                  "mla_kernel_work": work.attention_kernel_work(
                      cfg, seq, traffic["global_batch"]),
                  "layers": layers.reduce(split, ids),
                  "moe": moe_scope.reduce(split, ids),
                  "mla": mla_scope.reduce(split, ids)}
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    print(json.dumps({"info": {
        "window_steps": len(steps),
        "window_compiles": watch.events[watch.COMPILE],
        "window_events": dict(watch.events),
        "drops": straggler.drops[:n_check] if straggler else None,
        "moe_counters_last_checked_step": counters,
        "moe_held_rows_window_mean": held_rows,
        "moe_held_rows_expected": (tokens * cfg["num_experts_per_tok"]
                                   * cfg["n_routed_experts"]
                                   / cfg["experts_routed_over"]),
        "moe_layers": n_moe,
        "coded_sync_paths": ts.coded_sync_paths(
            MOE.split_bias(shapes["params"])[0], trainer.celeris, None),
        "longest_steps_s_and_call_s": [
            [s["s"], s["call_s"]] for s in
            sorted(steps, key=lambda s: s["s"])[-3:]],
        "outside_steps_s": t_w1 - t_w0 - sum(durations),
        "gc_s": watch.gc_s, "gc_longest_s": watch.gc_longest,
        "layers_ms_per_step": (layers.per_step_ms(record["layers"])
                               if record and record["layers"] else None),
        "moe": record["moe"] if record else None,
        "mla": record["mla"] if record else None}}),
        flush=True)

    # the reference, once the program's state is freed
    del trainer
    gc.collect()
    init32 = jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(jnp.float32), make(k)))
    ref = MoonlightMoE(cfg, traffic)
    key = jax.random.PRNGKey(seed)
    got = ref.steps(
        lambda: init32(wkey),
        [batches.global_batch(t) for t in range(n_check)],
        [jax.random.fold_in(key, t) for t in range(n_check)],
        straggler.drops[:n_check] if straggler else [0.0] * n_check)
    values, worst = checks.gaps(prog, got, names)
    values["router_bias_differing"] = bias_differing(
        prog_biases, got["biases"], cfg["bias_update_rate"])
    correct, chk = checks.judge(values, limits)
    top = sorted(zip(names, prog["change_norms"], got["change_norms"]),
                 key=lambda x: -abs(x[1] - x[2]) / max(x[2], 1e-30))[:3]
    print(json.dumps({"worst_leaf": worst, "program": prog["loss"],
                      "reference": got["loss"],
                      "change_norms_farthest": top,
                      "router_bias_absmax": [float(np.abs(prog_biases).max()),
                                             float(np.abs(got["biases"]).max())]
                      }), file=sys.stderr)
    return {"correct": correct and failed == 0, "attempted": len(steps),
            "failed": failed, "end_to_end": end_to_end, "device": device,
            "checks": chk, "record": record, "breakdown": breakdown}
