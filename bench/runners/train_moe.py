"""Train cells of a decoder LM whose layers are mixtures of experts: the
program's ``Trainer`` on Granite-3.0 MoE.

As ``bench/runners/train.py`` (set-up drives the window's one
``Trainer`` through the checked steps, the window calls
``Trainer.run(1)`` back to back, and after it the program's state is
freed and a reference repeats the checked steps), with:

- the widths, the MoE and Granite's four multipliers checked against
  the program's registry; the depth is the configuration's
  (``reduced``), the registry's the published one;
- the reference ``bench/reference/granite_moe.py``, and the model FLOPs
  of ``bench/roofline_moe.py``;
- before the window the chip's memory is compacted once (set-up): the
  step's state fills most of it, and a compaction inside the window
  (``TpuClient::DefragmentMemory``) stalls a step for 35 ms to seconds;
- with ``--trace 1``, the record keeps the split of the trace by the
  program's layers (``record["layers"]``, ``bench/layers.py``) and of
  its ``moe`` scope (``record["moe"]``, ``bench/moe_scope.py``), each
  operation named through the compiled step's HLO, and the expert
  matmuls' work per step (``record["experts_work"]``).

The routing decisions of the checked steps that the reference did not
make are counted by ``bench/control_moe.py``, which builds the same
program through :func:`start`.
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

from bench import checks, generator, harness, layers, moe_scope, roofline_moe
from bench import trace as trace_mod
from bench.runners.train import _Watch

# the configuration file's key for each ModelConfig field it pins
WIDTHS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
          "tie_embeddings": "tie_word_embeddings",
          "qkv_bias": "attention_bias",
          "embedding_multiplier": "embedding_multiplier",
          "attention_multiplier": "attention_multiplier",
          "residual_multiplier": "residual_multiplier",
          "logits_scaling": "logits_scaling"}
# ... and each MoEConfig field
MOE_WIDTHS = {"n_experts": "num_local_experts",
              "top_k": "num_experts_per_tok",
              "d_expert": "intermediate_size",
              "aux_weight": "aux_loss_coef",
              "router_z_weight": "router_z_loss_coef"}


def model_config(cfg: dict):
    """The program's ModelConfig for this configuration file: the
    registry's entry at the file's depth, with ``program`` settings
    applied (``expert_pad_multiple`` on its MoE), refused unless every
    width and multiplier equals the file's and the registry's depth is
    the published one."""
    import repro.configs as C
    name, _, variant = cfg["registry"].partition(":")
    base = C.get_smoke(name) if variant == "smoke" else C.get(name)
    program = dict(cfg.get("program", {}))
    pad = program.pop("expert_pad_multiple", base.moe.expert_pad_multiple)
    mc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"],
        moe=dataclasses.replace(base.moe, expert_pad_multiple=pad),
        **program)
    wrong = {f: (getattr(mc, f), cfg[k]) for f, k in WIDTHS.items()
             if getattr(mc, f) != cfg[k]}
    wrong.update({f"moe.{f}": (getattr(mc.moe, f), cfg[k])
                  for f, k in MOE_WIDTHS.items()
                  if getattr(mc.moe, f) != cfg[k]})
    depth = cfg["reduced"]["num_hidden_layers"]["published"]
    if base.n_layers != depth:
        wrong["n_layers"] = (base.n_layers, depth)
    if (mc.mlp_type != "swiglu" or cfg["hidden_act"] != "silu"
            or set(mc.block_pattern) != {"moe"} or mc.moe.n_shared):
        wrong["block"] = (mc.block_pattern, mc.mlp_type, cfg["hidden_act"])
    if wrong:
        raise ValueError(f"registry {cfg['registry']!r} differs from the "
                         f"configuration file: {wrong}")
    return mc


def start(cfg: dict, traffic: dict, seed: int):
    """The program's ``Trainer`` for this configuration and traffic, on
    the benchmark's weights and batches from ``seed``: ``(trainer,
    batches, straggler, make, wkey, shapes)``, with ``make(wkey)`` the
    initial weights and ``shapes`` the train state's layout."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig
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
        return {"params": params, "opt": adamw.init_opt_state(params),
                "step": jnp.zeros((), jnp.int32)}

    trainer.state = jax.jit(make_state)(wkey)
    return trainer, batches, straggler, make, wkey, shapes


def run_cell(*, cfg, traffic, limits, seed, seconds, trace, devices,
             t_start) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.train import train_step as ts

    from bench.reference.granite_moe import GraniteMoE

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
    counters = {k: last[k] for k in ("moe_load_max", "moe_dropped")}
    # each leaf's change in one program, which holds no second copy of
    # the weights beside the state
    change = jax.jit(lambda m, k: [
        jnp.sqrt(jnp.sum(jnp.square(a - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(m), jax.tree.leaves(make(k)))])
    prog["change_norms"] = [float(x) for x in change(
        trainer.state["opt"]["master"], wkey)]
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes["params"])[0]]
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
                          "call_s": last["wall_s"], "loss": last["loss"]})
    t_w1 = time.perf_counter()
    watch.detach()
    if trace:
        jax.profiler.stop_trace()
    device = harness.device_report(devices)
    failed = sum(1 for s in steps if not math.isfinite(s["loss"]))

    tokens = traffic["global_batch"] * traffic["seq_len"]
    durations = [s["s"] for s in steps]
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
        split = layers.load(path, hlo)
        tmp.cleanup()
        ids = [d.id for d in devices]
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["top_ops"],
                         "idle_gaps": red["idle_gaps"]}
        record = {"kind": "train", "steps": steps, "trace": red,
                  "chips": len(devices),
                  "device_kind": devices[0].device_kind,
                  "flops_per_step": roofline_moe.moe_lm_train_flops(
                      cfg, traffic["seq_len"], tokens),
                  "experts_work": roofline_moe.experts_work(
                      cfg, tokens, recompute=True),
                  "layers": layers.reduce(split, ids),
                  "moe": moe_scope.reduce(split, ids)}
    print(json.dumps({"info": {
        "window_steps": len(steps),
        "window_compiles": watch.events[watch.COMPILE],
        "window_events": dict(watch.events),
        "drops": straggler.drops[:n_check] if straggler else None,
        "moe_counters_last_checked_step": counters,
        "coded_sync_paths": ts.coded_sync_paths(
            shapes["params"], trainer.celeris, None),
        "longest_steps_s_and_call_s": [
            [s["s"], s["call_s"]] for s in
            sorted(steps, key=lambda s: s["s"])[-3:]],
        "outside_steps_s": t_w1 - t_w0 - sum(durations),
        "gc_s": watch.gc_s, "gc_longest_s": watch.gc_longest,
        "layers_ms_per_step": (layers.per_step_ms(record["layers"])
                               if record and record["layers"] else None),
        "moe": record["moe"] if record else None}}),
        flush=True)

    # the reference, once the program's state is freed
    del trainer
    gc.collect()
    init32 = jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(jnp.float32), make(k)))
    ref = GraniteMoE(cfg, traffic)
    key = jax.random.PRNGKey(seed)
    got = ref.steps(
        lambda: init32(wkey),
        [batches.global_batch(t) for t in range(n_check)],
        [jax.random.fold_in(key, t) for t in range(n_check)],
        straggler.drops[:n_check] if straggler else [0.0] * n_check)
    values, worst = checks.gaps(prog, got, names)
    correct, chk = checks.judge(values, limits)
    top = sorted(zip(names, prog["change_norms"], got["change_norms"]),
                 key=lambda x: -abs(x[1] - x[2]) / max(x[2], 1e-30))[:3]
    print(json.dumps({"worst_leaf": worst, "program": prog["loss"],
                      "reference": got["loss"],
                      "change_norms_farthest": top}), file=sys.stderr)
    return {"correct": correct and failed == 0, "attempted": len(steps),
            "failed": failed, "end_to_end": end_to_end, "device": device,
            "checks": chk, "record": record, "breakdown": breakdown}
