import os
import sys

# A simulated-mesh lowering tool: it lowers onto host CPU devices only
# and must never take an attached chip (whose one device would break
# every production mesh below).  Like the device count, this has to be
# set before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
# --scale-check needs 1024 simulated devices; everything else keeps the
# 512-device default (REPRO_DRYRUN_DEVICES overrides).
_N_DEV = int(os.environ.get(
    "REPRO_DRYRUN_DEVICES",
    1024 if "--scale-check" in sys.argv else 512))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_N_DEV} "
    # CPU-only workaround: AllReducePromotion CHECK-crashes on the
    # mixed-dtype variadic all-reduces the combiner builds from bf16
    # wire + f32 count syncs (irrelevant on TPU).
    "--xla_disable_hlo_passes=all-reduce-promotion")

"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: parameter
and activation shardings must partition, collectives must be legal on
the mesh, and the compiled module's memory analysis must fit the chips.

Usage:
    python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
    python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all          # every runnable cell, both meshes

Each run appends a JSON record (memory analysis, cost analysis,
collective-byte breakdown parsed from the post-SPMD HLO) to
``results/dryrun/<arch>__<shape>__<mesh>.json`` for EXPERIMENTS.md and
the roofline benchmark to consume.
"""
import argparse
import json
import re
import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro import sharding as shd
from repro.configs.base import SHAPES
from repro.launch import costs
from repro.launch import mesh as mesh_mod
from repro.launch import specs
from repro.optim import adamw
from repro.serve import serve_step
from repro.train import train_step as ts

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

def _serve_fn_args(cfg, shape, mesh):
    """(jitted fn, abstract args) for a serve cell — the single lowering
    recipe shared by lower_cell and serve_check_cell."""
    params = specs.abstract_params(cfg, mesh)
    if shape.kind == "prefill":
        batch = specs.prefill_input_specs(cfg, shape, mesh)
        return serve_step.make_prefill(cfg, shape.seq_len), (params, batch)
    if shape.kind == "decode":
        batch, caches, index = specs.decode_input_specs(cfg, shape, mesh)
        return serve_step.make_decode(cfg), (params, caches, batch, index)
    raise ValueError(f"{shape.name} is not a serve shape")


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               celeris: bool = True, quantize_wire: bool = False):
    cfg = C.get(arch)
    shape = SHAPES[shape_name]
    if shape_name not in C.runnable_shapes(cfg):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": ("latent attention has no KV cache here"
                           if cfg.mla is not None and shape.kind != "train"
                           else "long_500k needs sub-quadratic attention")}

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    shd.set_global_mesh(mesh)
    t0 = time.time()

    # gradient accumulation so multi-B-param train cells fit 16 GB HBM
    n_params = cfg.param_count()
    micro = 4 if n_params >= 6e9 else (2 if n_params >= 2e9 else 1)

    if shape.kind == "train":
        state = specs.abstract_state(cfg, mesh)
        batch = specs.train_input_specs(cfg, shape, mesh)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                   sharding=jax.sharding.NamedSharding(
                                       mesh, jax.sharding.PartitionSpec()))
        drop = jax.ShapeDtypeStruct((), jnp.float32,
                                    sharding=jax.sharding.NamedSharding(
                                        mesh, jax.sharding.PartitionSpec()))
        step_fn = ts.make_train_step(
            cfg, mesh, adamw.OptConfig(),
            ts.CelerisConfig(mode="lossy_hadamard" if celeris else "exact",
                             lossy_moe=celeris and cfg.moe is not None,
                             quantize_wire=quantize_wire),
            donate=True, microbatches=micro)
        lowered = step_fn.lower(state, batch, key, drop)
        jax_costs = costs.trace_costs(step_fn, state, batch, key, drop)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * cfg.active_param_count() * tokens
    else:   # prefill / decode
        fn, args = _serve_fn_args(cfg, shape, mesh)
        lowered = fn.lower(*args)
        jax_costs = costs.trace_costs(fn, *args)
        if shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
            model_flops = 2.0 * cfg.active_param_count() * tokens
        else:
            model_flops = 2.0 * cfg.active_param_count() * shape.global_batch

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    colls = costs.hlo_collective_bytes(compiled.as_text())

    n_dev = mesh.devices.size
    coll_per_dev = colls.get("total_bytes", 0.0)
    rl = costs.roofline(jax_costs["flops"], jax_costs["hbm_bytes"],
                        coll_per_dev, int(n_dev), model_flops,
                        mesh_mod.HW)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
        "celeris": celeris,
        "kind": shape.kind,
        "microbatches": micro if shape.kind == "train" else None,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
                          + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
        "cost": {k: cost.get(k) for k in
                 ("flops", "bytes accessed", "transcendentals",
                  "bytes accessed output")} if cost else {},
        "collectives": {k: v for k, v in colls.items()
                        if k != "total_bytes"},
        "collective_bytes_total": coll_per_dev,
        "jaxpr_costs": jax_costs,
        "model_flops": model_flops,
        "roofline": rl,
    }
    return rec


# ----------------------------------------------------------------------
# Transport-coupled scale check: lower the lossy(+Hadamard) train step
# on simulated 512- and 1024-device meshes and prove the emitted program
# contains nothing but PLAIN collectives — the paper's §III-B claim that
# best-effort transport changes no compiler contract: Celeris semantics
# live entirely in elementwise masking + unbiasing around ordinary
# psum / all_gather / all_to_all.
# ----------------------------------------------------------------------

# every collective-ish StableHLO/HLO op we could possibly emit
_COLLECTIVE_OPS = (
    "all_reduce", "all_gather", "all_to_all", "reduce_scatter",
    "collective_permute", "collective_broadcast", "partition_id",
    "replica_id", "send", "recv",
)
_PLAIN_COLLECTIVES = {"all_reduce", "all_gather", "all_to_all",
                      "reduce_scatter"}


def collective_ops_in(text: str):
    """{op_name: count} over the collective ops present in lowered IR.

    Matches both spellings: StableHLO underscores (``all_reduce``, what
    ``lower().as_text()`` emits) and post-SPMD HLO hyphens
    (``all-reduce``, what ``compile().as_text()`` emits).
    """
    out = {}
    for op in _COLLECTIVE_OPS:
        pat = op.replace("_", "[-_]")
        n = len(re.findall(rf"\b(?:stablehlo\.|mhlo\.)?{pat}\b", text))
        if n:
            out[op] = n
    return out


def scale_check_cell(arch: str, n_devices: int, mode: str = "lossy_hadamard",
                     shape_name: str = "train_4k"):
    """Lower (no compile) one lossy train-step cell at ``n_devices``."""
    from repro.core.transport.coupling import CollectiveMode

    cfg = C.get(arch)
    shape = SHAPES[shape_name]
    mesh = mesh_mod.make_scale_mesh(n_devices)
    shd.set_global_mesh(mesh)
    t0 = time.time()
    state = specs.abstract_state(cfg, mesh)
    batch = specs.train_input_specs(cfg, shape, mesh)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=jax.sharding.NamedSharding(
                                   mesh, jax.sharding.PartitionSpec()))
    # hierarchical mode takes the per-pod (n_pods + 1,) drop vector
    # ([intra_pod..., cross], coupling.AxisSchedules.per_pod) so the
    # scale check lowers the per-pod mask-rate combine too; other
    # modes take the scalar
    drop_shape = ()
    if CollectiveMode.parse(mode).hierarchical:
        n_pods = mesh.shape.get(shd.POD_AXIS, 1)
        if n_pods > 1:
            drop_shape = (n_pods + 1,)
    drop = jax.ShapeDtypeStruct(drop_shape, jnp.float32,
                                sharding=jax.sharding.NamedSharding(
                                    mesh, jax.sharding.PartitionSpec()))
    step_fn = ts.make_train_step(
        cfg, mesh, adamw.OptConfig(),
        ts.CelerisConfig(mode=mode,
                         lossy_moe=(CollectiveMode.parse(mode).lossy
                                    and cfg.moe is not None)),
        donate=True)
    lowered = step_fn.lower(state, batch, key, drop)
    t_lower = time.time() - t0
    colls = collective_ops_in(lowered.as_text())
    illegal = {k: v for k, v in colls.items()
               if k not in _PLAIN_COLLECTIVES}
    rec = {
        "arch": arch, "shape": shape_name, "mode": mode,
        "n_devices": n_devices,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "dp_degree": int(mesh.devices.size) // 16,
        "lower_s": round(t_lower, 1),
        "collective_ops": colls,
        "illegal_collectives": illegal,
        "ok": not illegal and "all_reduce" in colls,
    }
    return rec


def scale_check(n_devices_list=(512, 1024), arch: str = "qwen2-0.5b",
                mode: str = "lossy_hadamard"):
    recs = []
    for n in n_devices_list:
        rec = scale_check_cell(arch, n, mode=mode)
        recs.append(rec)
        print(f"{'OK ' if rec['ok'] else 'BAD'} {arch} {mode} "
              f"n_devices={n} mesh={rec['mesh']} "
              f"lower={rec['lower_s']}s collectives={rec['collective_ops']}",
              flush=True)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"scale_check__{arch}__{mode}.json")
    with open(path, "w") as f:
        json.dump(recs, f, indent=1)
    print(f"saved -> {path}")
    if not all(r["ok"] for r in recs):
        raise SystemExit("scale check FAILED: non-plain collectives "
                         "in the lowered lossy train step")
    return recs


# ----------------------------------------------------------------------
# Serve-path dry run: lower prefill + decode on single- and multi-pod
# meshes and prove the emitted programs carry nothing but plain
# collectives — the serving analogue of --scale-check (the serve path
# never opens a shard_map island, so any exotic op here would mean the
# GSPMD specs leak manual collectives).
# ----------------------------------------------------------------------

def serve_check_cell(arch: str, shape_name: str, multi_pod: bool):
    """Lower AND compile one serve cell; census the post-SPMD HLO.

    Unlike the train island (whose collectives are explicit at trace
    time), the serve path is pure GSPMD — the partitioner inserts its
    collectives during compile, so the check must read the compiled
    module's HLO, not the lowered StableHLO.
    """
    cfg = C.get(arch)
    shape = SHAPES[shape_name]
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    shd.set_global_mesh(mesh)
    t0 = time.time()
    fn, args = _serve_fn_args(cfg, shape, mesh)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    colls = collective_ops_in(compiled.as_text())
    # post-SPMD HLO always contains partition-id: GSPMD addresses each
    # device's shard via dynamic-slice(partition-id) — compiler-internal
    # bookkeeping, not a collective.  (The train scale-check censuses
    # pre-SPMD StableHLO, where partition_id WOULD mean a manual
    # lowering leaked; it stays strict.)
    benign = _PLAIN_COLLECTIVES | {"partition_id", "replica_id"}
    illegal = {k: v for k, v in colls.items() if k not in benign}
    return {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "collective_ops": colls,
        "illegal_collectives": illegal,
        # TP (model-sharded matmuls) must reduce somewhere: a census
        # with no all_reduce/reduce_scatter at all means the specs
        # silently replicated the weights
        "ok": not illegal and any(k in colls for k in
                                  ("all_reduce", "reduce_scatter")),
    }


def serve_check(arch: str = "qwen2-0.5b",
                shapes=("prefill_32k", "decode_32k")):
    recs = []
    for multi_pod in (False, True):
        for shape_name in shapes:
            rec = serve_check_cell(arch, shape_name, multi_pod)
            recs.append(rec)
            print(f"{'OK ' if rec['ok'] else 'BAD'} {arch} {shape_name:12s} "
                  f"mesh={rec['mesh']:8s} lower={rec['lower_s']}s "
                  f"collectives={rec['collective_ops']}", flush=True)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"serve_check__{arch}.json")
    with open(path, "w") as f:
        json.dump(recs, f, indent=1)
    print(f"saved -> {path}")
    if not all(r["ok"] for r in recs):
        raise SystemExit("serve check FAILED: non-plain collectives in "
                         "the lowered serve path")
    return recs


def run_and_save(arch, shape_name, multi_pod, celeris=True,
                 quantize_wire=False):
    rec = lower_cell(arch, shape_name, multi_pod, celeris, quantize_wire)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = f"{C.canonical(arch)}__{shape_name}__" \
          f"{'2x16x16' if multi_pod else '16x16'}"
    path = os.path.join(RESULTS_DIR, tag + ".json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec, path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str)
    ap.add_argument("--shape", type=str)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-celeris", action="store_true",
                    help="baseline (exact collectives) variant")
    ap.add_argument("--quantize-wire", action="store_true",
                    help="H6: int8 wire w/ s16 reduction")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--scale-check", action="store_true",
                    help="lower the lossy train step at 512 and 1024 "
                         "simulated devices; assert plain collectives only")
    ap.add_argument("--serve-check", action="store_true",
                    help="lower prefill + decode on the single- and "
                         "multi-pod production meshes; assert plain "
                         "collectives only")
    ap.add_argument("--mode", type=str, default="lossy_hadamard",
                    help="collective mode for --scale-check")
    args = ap.parse_args()

    if args.scale_check:
        scale_check(arch=args.arch or "qwen2-0.5b", mode=args.mode)
        return

    if args.serve_check:
        serve_check(arch=args.arch or "qwen2-0.5b")
        return

    if args.all:
        cells = []
        for arch in C.ARCHS:
            cfg = C.get(arch)
            for shape_name in C.runnable_shapes(cfg):
                for mp in (False, True):
                    cells.append((arch, shape_name, mp))
        failures = 0
        for arch, shape_name, mp in cells:
            try:
                rec, _ = run_and_save(arch, shape_name, mp,
                                      celeris=not args.no_celeris)
                mm = rec["memory"]["peak_bytes"]
                print(f"OK  {arch:24s} {shape_name:12s} "
                      f"{'2x16x16' if mp else '16x16':8s} "
                      f"compile={rec['compile_s']:7.1f}s "
                      f"peak/dev={mm/2**30:6.2f}GiB "
                      f"coll={rec['collective_bytes_total']/2**20:8.1f}MiB",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"FAIL {arch} {shape_name} mp={mp}: "
                      f"{type(e).__name__}: {e}", flush=True)
        sys.exit(1 if failures else 0)

    rec, path = run_and_save(args.arch, args.shape, args.multi_pod,
                             celeris=not args.no_celeris,
                             quantize_wire=args.quantize_wire)
    print(json.dumps(rec, indent=1))
    print(f"saved -> {path}")


if __name__ == "__main__":
    main()
