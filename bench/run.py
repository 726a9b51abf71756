"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload qwen2-0.5b.coded --seed 7 \
        --seconds 30 --trace 0

The cell, its configuration, traffic mix, limits and per-layer metric
readers are found by name (see ``bench/harness.py``).  The run refuses
anything but a TPU with as many chips as the cell asks for: it then
exits with code 2 and prints no result.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` traces a shorter window with
the profiler and reports its per-layer metrics.  The last line of
standard output is the result: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (with ``--trace 1`` also ``breakdown``), then
``checks``, each number of the correctness comparison beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, *, require_tpu: bool = True, t_start: float = T_START,
        root: str = ROOT) -> dict:
    """One run of one cell; returns the result as a dict.

    ``require_tpu=False`` skips the look for a chip (CPU rehearsals)."""
    man = harness.manifest(root)
    wl = harness.workload(man, args.workload)
    cfg = harness.config_file(man, wl["config"], root)
    bench_dir = os.path.join(root, "bench")
    traffic = harness.traffic_file(wl["traffic"], bench_dir)
    limits = harness.limits_file(wl["name"], bench_dir)
    if require_tpu:
        devices = harness.check_devices(wl["chips"])
        harness.enable_compile_cache()
    else:
        import jax
        devices = jax.devices()[:wl["chips"]]
    res = harness.runner(cfg["runner"], bench_dir).run_cell(
        cfg=cfg, traffic=traffic, limits=limits, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), devices=devices,
        t_start=t_start)
    metrics = {}
    for m in harness.cell_metrics(man, wl, bool(args.trace)):
        if args.trace:
            value = harness.metric_reader(m["name"], bench_dir)(
                res["record"])
        else:
            value = res["end_to_end"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": res["device"], "checks": res["checks"],
            "breakdown": res.get("breakdown") if args.trace else None}


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_checks(out["checks"])
    print(harness.result_line(**out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
