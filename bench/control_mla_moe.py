"""Readings that the latent-attention MoE train cells' limits are set
against: the control and the planted fault, as ``bench/control.py``
takes them for the dense cells, with the reference
``bench/reference/moonlight.py``.

    python3 bench/control_mla_moe.py \\
        --workload moonlight-16b-a3b.coded-8k --seeds 11,12,13

Per seed one JSON line holds the numbers of ``control`` (the reference
in float8: e4m3 forward, e5m2 gradients, per-tensor scales) and
``half_batch`` (half of the batch's rows left out, or with one row half
of its tokens), each compared with the float32 reference by
``bench/checks.py``, and the share of router-bias entries on which each
ends the checked steps differently from the float32 reference.  The
benchmark's own runs never run this; it runs on one chip at the cell's
size.  Drop rates are the straggler model's draws at the timeout
controller's initial timeout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import checks, generator, harness  # noqa: E402
from bench.control import INIT_TIMEOUT  # noqa: E402


def readings(cfg: dict, traffic: dict, seed: int, shapes,
             faults=("control", "half_batch")) -> dict:
    """``{fault: (numbers, worst leaves)}`` for one seed, the share of
    differing router-bias entries among the numbers as
    ``router_bias_differing``; ``shapes`` is the parameter layout (a
    pytree of ``ShapeDtypeStruct``)."""
    import jax
    import jax.numpy as jnp

    from bench.reference.moonlight import MoonlightMoE, split_bias
    from bench.runners.train_mla_moe import bias_differing

    n = int(traffic["checked_steps"])
    make, wkey = generator.weight_init(shapes, seed, cfg["initializer_range"])
    init = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(jnp.float32),
                                          make(k)))
    batches = generator.TokenBatches(traffic, cfg["vocab_size"], seed)
    straggler = (generator.Straggler(traffic["straggler"], seed)
                 if traffic.get("straggler") else None)
    drops = ([straggler.drop_rate(INIT_TIMEOUT) for _ in range(n)]
             if straggler else [0.0] * n)
    key = jax.random.PRNGKey(seed)
    args = ([batches.global_batch(t) for t in range(n)],
            [jax.random.fold_in(key, t) for t in range(n)], drops)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(split_bias(shapes)[0])[0]]
    ref32 = MoonlightMoE(cfg, traffic)
    base = ref32.steps(lambda: init(wkey), *args)
    out = {}
    for fault in faults:
        if fault == "control":
            got = MoonlightMoE(cfg, traffic, precision="float8").steps(
                lambda: init(wkey), *args)
        else:
            got = ref32.steps(lambda: init(wkey), *args, half=True)
        numbers, worst = checks.gaps(got, base, names)
        numbers["router_bias_differing"] = bias_differing(
            got["biases"], base["biases"], cfg["bias_update_rate"])
        out[fault] = (numbers, worst)
    return out


def param_shapes(cfg: dict):
    """The program's parameter layout for this configuration."""
    import jax

    from bench.runners.train_mla_moe import model_config
    from repro.models import model as M
    mc = model_config(cfg)
    return jax.eval_shape(lambda k: M.init_params(k, mc),
                          jax.random.PRNGKey(0))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    man = harness.manifest()
    wl = harness.workload(man, args.workload)
    cfg = harness.config_file(man, wl["config"])
    traffic = harness.traffic_file(wl["traffic"])
    harness.check_devices(1)
    harness.enable_compile_cache()
    shapes = param_shapes(cfg)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = {k: {"numbers": v[0], "worst_leaf": v[1]} for k, v in
               readings(cfg, traffic, seed, shapes).items()}
        print(json.dumps({"workload": wl["name"], "seed": seed, **res}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
