"""Train cells: the program's ``Trainer`` on a dense decoder LM.

Set-up builds one ``Trainer`` (the program's step, timeout controller
and host loop), hands it the benchmark's weights (made on the device in
one jitted call from ``--seed``), token batches and straggler draws,
and drives it through the checked steps with ``Trainer.run(1)``: they
compile or load every program the window runs.  The window then calls
``Trainer.run(1)`` back to back; a step is begun while the window is
open, and the window closes at the end of the last one begun.  Each
step ends at the Trainer's own sync (it reads the step's metrics).

After the window the program's state is freed and the reference
(``bench/reference/dense_lm.py``) repeats the checked steps from the
same weights, batches, keys and drop rates; ``bench/checks.py``
compares.  An ``info`` line before the result gives the window's steps
and JAX's events in it (compilations among them), the checked steps'
drop rates, the three longest steps each with its step call's part, the
window's time between steps and the garbage collector's pauses.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import math
import sys
import tempfile
import time

import numpy as np

from bench import checks, generator, harness, roofline
from bench import trace as trace_mod

# the configuration file's key for each ModelConfig field it pins
WIDTHS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
          "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads",
          "d_ff": "intermediate_size", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
          "tie_embeddings": "tie_word_embeddings",
          "qkv_bias": "qkv_bias"}


def model_config(cfg: dict):
    """The program's ModelConfig for this configuration file: the
    registry's entry with ``program`` settings applied, refused unless
    every width equals the file's."""
    import repro.configs as C
    name, _, variant = cfg["registry"].partition(":")
    base = C.get_smoke(name) if variant == "smoke" else C.get(name)
    mc = dataclasses.replace(base, **cfg.get("program", {}))
    wrong = {f: (getattr(mc, f), cfg[k]) for f, k in WIDTHS.items()
             if getattr(mc, f) != cfg[k]}
    if mc.mlp_type != "swiglu" or cfg["hidden_act"] != "silu":
        wrong["mlp"] = (mc.mlp_type, cfg["hidden_act"])
    if wrong:
        raise ValueError(f"registry {cfg['registry']!r} differs from the "
                         f"configuration file: {wrong}")
    return mc


class _Watch:
    """What the host does besides the steps while attached: JAX's
    monitoring events by name (a compilation, a cache load or a trace
    shows here) and the garbage collector's pauses."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.events = collections.Counter()
        self.gc_s, self.gc_longest, self._t0 = 0.0, 0.0, None
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        gc.callbacks.append(self._gc)

    def _event(self, event: str, **_):
        self.events[event] += 1

    def _timed(self, event: str, duration: float, **_):
        self.events[event] += 1

    def _gc(self, phase: str, info: dict):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            d = time.perf_counter() - self._t0
            self.gc_s, self.gc_longest = self.gc_s + d, max(self.gc_longest,
                                                            d)
            self._t0 = None

    def detach(self):
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._timed)
        gc.callbacks.remove(self._gc)


def run_cell(*, cfg, traffic, limits, seed, seconds, trace, devices,
             t_start) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig
    from repro.optim import adamw
    from repro.optim.adamw import OptConfig
    from repro.train import train_step as ts
    from repro.train.trainer import Trainer

    from bench.reference.dense_lm import DenseLM

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
    init32 = jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(jnp.float32), make(k)))
    diff = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
    prog["change_norms"] = checks.leaf_norms(
        diff(trainer.state["opt"]["master"], init32(wkey)))
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes["params"])[0]]

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
        raw = trace_mod.load(trace_mod.xplane_path(tmp.name))
        tmp.cleanup()
        red = trace_mod.reduce(raw, [d.id for d in devices])
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["top_ops"],
                         "idle_gaps": red["idle_gaps"]}
        record = {"kind": "train", "steps": steps, "trace": red,
                  "chips": len(devices),
                  "device_kind": devices[0].device_kind,
                  "flops_per_step": roofline.dense_lm_train_flops(
                      cfg, traffic["seq_len"], tokens)}
    print(json.dumps({"info": {
        "window_steps": len(steps),
        "window_compiles": watch.events[watch.COMPILE],
        "window_events": dict(watch.events),
        "drops": straggler.drops[:n_check] if straggler else None,
        "longest_steps_s_and_call_s": [
            [s["s"], s["call_s"]] for s in
            sorted(steps, key=lambda s: s["s"])[-3:]],
        "outside_steps_s": t_w1 - t_w0 - sum(durations),
        "gc_s": watch.gc_s, "gc_longest_s": watch.gc_longest}}),
        flush=True)

    # the reference, once the program's state is freed
    del trainer
    gc.collect()
    ref = DenseLM(cfg, traffic)
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
