"""Benchmark driver: one function per paper table/figure.

Prints ``name,value,paper_reference`` CSV at the end and merges the
machine-readable metrics into ``BENCH_sim.json`` next to the repo root
for CI consumption (merge, not overwrite, so the full run and the smoke
run can share one committed baseline file).

Every invocation also writes a **run manifest** to
``results/manifest_<tier>.json`` — git sha, a hash of every section's
parameters, per-section wall-clock and row counts — so any figure
number in the baseline can be traced back to the exact code + config
that produced it (see ``docs/OBSERVABILITY.md``).  Per-section
wall-clock also lands in the CSV/JSON as ``timing_<section>_wall_s``
rows (the ``_wall_s`` suffix is regression-exempt: machine-dependent).

Tiers:
- default      — every table/figure at paper scale (several minutes);
- ``--quick``  — shrunk rounds/steps, no sequential-reference timing,
  no 512/1024-node sweep tiers;
- ``--smoke``  — the CI tier (aims for about a minute): 32-node engine
  A/B against the sequential reference, kernel micro-bench, and a tiny
  engine-driven e2e lossy train step.  Same code paths, same JSON
  schema, ``smoke_``-prefixed keys.

``--out PATH`` writes the JSON elsewhere (CI uses this to compare a
fresh smoke run against the committed baseline via
``benchmarks/check_regression.py``).
"""
import hashlib
import json
import os
import subprocess
import sys
import time

# allow both `python -m benchmarks.run` and `python benchmarks/run.py`
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# rows with these prefixes are persisted to BENCH_sim.json (most are
# deterministic simulation metrics the regression gate compares;
# check_regression.py separately skips the _wall_s/_us/kernel timing
# keys, which are machine-dependent)
_KEY_PREFIXES = ("engine_", "fig1e2e_", "fig2_", "fig3_", "fig4_", "fig5_",
                 "fig6_", "fig7_", "fig8_", "fig9_", "fig10_", "kernel_",
                 "smoke_", "timing_")

_DEFAULT_OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_sim.json")


def _git_sha() -> str:
    """Current commit (+'-dirty' when the tree differs); 'unknown' when
    git is unavailable — the manifest must never fail the run."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_REPO_ROOT, timeout=10,
            capture_output=True, text=True)
        if sha.returncode != 0:
            return "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=_REPO_ROOT, timeout=10,
            capture_output=True, text=True)
        mark = "-dirty" if dirty.returncode == 0 and dirty.stdout.strip() \
            else ""
        return sha.stdout.strip() + mark
    except (OSError, subprocess.SubprocessError):
        return "unknown"


class _Sections:
    """Collects benchmark rows per named section, timing each one for
    the run manifest and the ``timing_*_wall_s`` rows."""

    def __init__(self):
        self.rows = []
        self.entries = []

    def add(self, name, fn, **kwargs):
        t0 = time.perf_counter()
        out = fn(**kwargs)
        dt = round(time.perf_counter() - t0, 2)
        if isinstance(out, tuple):       # fig3 returns (rows, extras)
            out = out[0]
        self.entries.append({"name": name, "wall_s": dt,
                             "kwargs": kwargs, "n_rows": len(out)})
        self.rows += out
        self.rows.append((f"timing_{name}_wall_s", dt, None))
        return out


def run_full(quick: bool) -> _Sections:
    from benchmarks import (table1_qp_state, table2_resources,
                            engine_backend, fig2_tail_latency,
                            fig1_e2e_loss_tolerance, fig3_scale_sweep,
                            fig4_cross_pod_tail, fig5_schedule_tail,
                            fig6_scale_schedule, fig7_fault_resilience,
                            fig8_serving_tail, fig9_tail_attribution,
                            fig10_priority_loss, kernel_bench, roofline)
    s = _Sections()
    s.add("table1", table1_qp_state.run)
    s.add("table2", table2_resources.run)
    s.add("fig2", fig2_tail_latency.run, n_rounds=120 if quick else 300,
          bench_sequential=not quick)
    s.add("fig3", fig3_scale_sweep.run,
          n_rounds=60 if quick else 120,
          seeds=(0, 1) if quick else (0, 1, 2, 3),
          n_nodes=(128, 256) if quick else (128, 256, 512, 1024))
    s.add("fig1e2e", fig1_e2e_loss_tolerance.run, steps=25 if quick else 60)
    s.add("fig4", fig4_cross_pod_tail.run, steps=25 if quick else 40,
          n_rounds=60 if quick else 100)
    s.add("fig5", fig5_schedule_tail.run, n_rounds=60 if quick else 100)
    s.add("fig6", fig6_scale_schedule.run,
          n_rounds=40 if quick else 60,
          n_nodes=(128, 512) if quick else fig6_scale_schedule.NODES)
    s.add("fig7", fig7_fault_resilience.run, steps=25 if quick else 40,
          n_rounds=40 if quick else 60, scale_cell=not quick)
    s.add("fig8", fig8_serving_tail.run, n_rounds=120 if quick else 300)
    s.add("fig9", fig9_tail_attribution.run)
    s.add("fig10", fig10_priority_loss.run,
          n_rounds=25 if quick else 40,
          n_nodes=(128, 256) if quick else fig10_priority_loss.NODES)
    s.add("kernels", kernel_bench.run)
    s.add("roofline", roofline.run)
    s.add("engine", engine_backend.run)
    return s


def run_smoke() -> _Sections:
    """CI tier: one engine A/B + kernels + one e2e lossy step + one
    2-pod topology case + one ring-vs-hier schedule A/B + one
    window-policy (round-vs-phase) A/B + one stall fault-injection
    cell + one serving incast sweep + one recorded tail-attribution
    cell + one priority-vs-arrival cut A/B (its high-priority loss
    ratio is floor-gated at 1.0x) + one jax-vs-numpy engine-backend
    throughput cell (its speedup key is floor-gated at 1.0x), about a
    minute, exercising the same code paths as the full run."""
    from benchmarks import (engine_backend, fig2_tail_latency,
                            fig1_e2e_loss_tolerance, fig4_cross_pod_tail,
                            fig5_schedule_tail, fig6_scale_schedule,
                            fig7_fault_resilience, fig8_serving_tail,
                            fig9_tail_attribution, fig10_priority_loss,
                            kernel_bench)
    from repro.core.transport import SimParams, NetworkParams
    s = _Sections()
    s.add("fig2", fig2_tail_latency.run,
          n_rounds=60, bench_sequential=True,
          params=SimParams(net=NetworkParams(n_nodes=32,
                                             burst_on_prob=0.0008)),
          prefix="smoke_fig2")
    s.add("fig1e2e", fig1_e2e_loss_tolerance.run, steps=6, smoke=True,
          prefix="smoke_fig1e2e")
    s.add("fig4", fig4_cross_pod_tail.run, smoke=True, prefix="smoke_fig4")
    s.add("fig5", fig5_schedule_tail.run, smoke=True, prefix="smoke_fig5")
    s.add("fig6", fig6_scale_schedule.run, smoke=True, prefix="smoke_fig6")
    s.add("fig7", fig7_fault_resilience.run, smoke=True,
          prefix="smoke_fig7")
    s.add("fig8", fig8_serving_tail.run, smoke=True, prefix="smoke_fig8")
    s.add("fig9", fig9_tail_attribution.run, smoke=True,
          prefix="smoke_fig9")
    s.add("fig10", fig10_priority_loss.run, smoke=True,
          prefix="smoke_fig10")
    s.add("kernels", lambda: [
        (f"smoke_{n}" if n.startswith("kernel_") else n, v, r)
        for n, v, r in kernel_bench.run()])
    s.add("engine", engine_backend.run, smoke=True)
    return s


def write_manifest(sections: _Sections, tag: str, out_path: str,
                   total_wall_s: float) -> str:
    """``results/manifest_<tier>.json``: enough provenance to re-derive
    (or distrust) every number the run merged into the baseline."""
    # the params hash covers section names + kwargs: two runs with the
    # same hash ran the same figure protocol (repr() covers SimParams
    # and other non-JSON kwargs deterministically)
    spec = [{"name": e["name"], "kwargs": e["kwargs"]}
            for e in sections.entries]
    spec_json = json.dumps(spec, sort_keys=True, default=repr)
    manifest = {
        "generator": "benchmarks/run.py",
        "tier": tag,
        "git_sha": _git_sha(),
        "params_hash": hashlib.sha256(spec_json.encode()).hexdigest()[:16],
        "argv": sys.argv[1:],
        "out_path": os.path.relpath(out_path, _REPO_ROOT),
        "python": sys.version.split()[0],
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "total_wall_s": round(total_wall_s, 1),
        "sections": [{**e, "kwargs": {k: v if isinstance(
            v, (int, float, str, bool, type(None))) else repr(v)
            for k, v in e["kwargs"].items()}} for e in sections.entries],
    }
    path = os.path.join(_REPO_ROOT, "results", f"manifest_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    return path


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    quick, smoke = args.quick, args.smoke
    out_path = args.out or _DEFAULT_OUT
    if quick and args.out is None:
        # the quick tier reuses full-run key names at shrunk protocol
        # scales — merging it into the committed baseline would corrupt
        # the CI regression gate
        out_path = _DEFAULT_OUT.replace(".json", "_quick.json")
        print(f"[--quick] writing to {out_path} so the committed "
              "baseline keeps full-protocol values")

    t_start = time.perf_counter()
    sections = run_smoke() if smoke else run_full(quick)
    rows = sections.rows

    print("\nname,value,paper_reference")
    for name, val, ref in rows:
        print(f"{name},{val},{'' if ref is None else ref}")

    bench = {}
    if os.path.exists(out_path):        # merge so full + smoke coexist
        try:
            with open(out_path) as f:
                bench = json.load(f)
        except (json.JSONDecodeError, OSError):
            bench = {}
    bench.update({name: val for name, val, _ in rows
                  if name.startswith(_KEY_PREFIXES)})
    tag = "smoke" if smoke else ("quick" if quick else "full")
    total = time.perf_counter() - t_start
    bench[f"total_bench_wall_s_{tag if tag != 'quick' else 'full'}"] = \
        round(total, 1)
    bench.pop("total_bench_wall_s", None)   # legacy key
    bench.pop("quick", None)
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=1, sort_keys=True)
    print(f"\nwrote {out_path}")
    mpath = write_manifest(sections, tag, out_path, total)
    print(f"wrote {mpath}")


if __name__ == "__main__":
    main()
