"""What every cell shares: finding its files by name, the device checks,
and the result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the configuration (``runner`` names
  the module under ``bench/runners/`` that drives it);
- ``bench/traffic/<traffic>.json``: the traffic mix that runner reads;
- ``bench/limits/<workload>.json``: the limits of the cell's
  correctness comparison;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric,
  ``read(record) -> float | None``.

A new cell, configuration, traffic mix or metric is a new file and a
new entry in ``BENCHMARK.json``; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(man: dict, name: str, root: str = ROOT) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_file(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def limits_file(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(bench_dir, "limits", f"{name}.json"))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(name: str, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "runners", f"{name}.py"),
                       f"bench_runner_{name}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    return load_module(path, "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_")).read


def cell_metrics(man: dict, wl: dict, trace: bool) -> list:
    """The metrics a run of this cell reports: its end-to-end ones, or
    with ``trace`` its per-layer ones, as ``BENCHMARK.json`` lists them."""
    e2e = [m for m in man["end_to_end"]
           if wl["name"] in m.get("workloads", [wl["name"]])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in man["per_layer"]
            if m["moves"] in reported
            and wl["name"] in m.get("workloads", [wl["name"]])]


def check_devices(chips: int) -> list:
    """The devices a cell runs on; refuses anything but enough TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX's first device is {devs[0].platform!r}, not a "
                       "TPU: no result is reported")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def device_report(devices) -> dict:
    import jax
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def enable_compile_cache() -> str:
    """JAX's persistent cache, in the program's fixed directory inside
    the checkout (or ``$JAX_COMPILATION_CACHE_DIR``); every program is
    kept, so a second run of a cell compiles nothing."""
    import jax
    from repro.launch import compile_cache
    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    """The closing JSON line; ``checks`` (each compared number beside
    its limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def print_checks(checks: dict) -> None:
    """Each compared number beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
