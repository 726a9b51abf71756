"""Readings that the MoE train cells' limits are set against: the
control and the planted fault, as ``bench/control.py`` takes them for
the dense cells, with the reference ``bench/reference/granite_moe.py``.

    python3 bench/control_moe.py --workload granite-moe-3b-a800m.coded-2k \\
        --seeds 11,12,13

Per seed one JSON line holds the numbers of ``control`` (the reference
in float8: e4m3 forward, e5m2 gradients, per-tensor scales) and
``half_batch`` (half of the batch's rows left out), each compared with
the float32 reference by ``bench/checks.py``, and the share of routing
decisions each made differently from the float32 reference; and under
``program`` the routing decisions of the program's own checked steps
(the cell's ``Trainer``, ``bench/runners/train_moe.py``) that the
float32 reference, given the same drop rates, did not make: bf16
near-ties flip.  The benchmark's own runs never run this; it runs on
one chip at the cell's size.  The control's and the fault's drop rates
are the straggler model's draws at the timeout controller's initial
timeout.
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
    """``{fault: (numbers, worst leaves, routing flips)}`` for one seed;
    ``shapes`` is the parameter layout (a pytree of
    ``ShapeDtypeStruct``)."""
    import jax
    import jax.numpy as jnp

    from bench.reference.granite_moe import GraniteMoE, routing_flips

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
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    ref32 = GraniteMoE(cfg, traffic)
    base = ref32.steps(lambda: init(wkey), *args)
    out = {}
    for fault in faults:
        if fault == "control":
            got = GraniteMoE(cfg, traffic, precision="float8").steps(
                lambda: init(wkey), *args)
            flips = routing_flips(got["routes"], base["routes"])
        else:
            got = ref32.steps(lambda: init(wkey), *args, half=True)
            flips = None
        out[fault] = checks.gaps(got, base, names) + (flips,)
    return out


def program_flips(cfg: dict, traffic: dict, seed: int) -> dict:
    """The program's routing decisions over the cell's checked steps
    that the float32 reference did not make (``routing_flips``): before
    each step, the program's forward pass at the step's weights and
    batch gives every MoE layer's expert ids.  The program's state is
    freed before the reference runs."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.reference.granite_moe import GraniteMoE, routing_flips
    from bench.runners.train_moe import start
    from repro.models import model as M

    n = int(traffic["checked_steps"])
    trainer, batches, straggler, make, wkey, _ = start(cfg, traffic, seed)
    mc = trainer.cfg
    routes_of = jax.jit(lambda p, b: M.forward(
        p, mc, b, remat=False, routes=True)[3]["moe_routes"])
    routes = []
    for t in range(n):
        batch = {k: jnp.asarray(v) for k, v in
                 batches.global_batch(t).items()}
        routes.append(np.asarray(routes_of(trainer.state["params"], batch)))
        del batch
        trainer.run(1)
    drops = straggler.drops[:n] if straggler else [0.0] * n
    del trainer
    gc.collect()
    init = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(jnp.float32),
                                          make(k)))
    key = jax.random.PRNGKey(seed)
    got = GraniteMoE(cfg, traffic).steps(
        lambda: init(wkey), [batches.global_batch(t) for t in range(n)],
        [jax.random.fold_in(key, t) for t in range(n)], drops)
    return routing_flips(routes, got["routes"])


def param_shapes(cfg: dict):
    """The program's parameter layout for this configuration."""
    import jax

    from bench.runners.train_moe import model_config
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
        flips = program_flips(cfg, traffic, seed)
        res = {k: {"numbers": v[0], "worst_leaf": v[1], "routing_flips": v[2]}
               for k, v in readings(cfg, traffic, seed, shapes).items()}
        print(json.dumps({"workload": wl["name"], "seed": seed,
                          "program": {"routing_flips": flips}, **res}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
