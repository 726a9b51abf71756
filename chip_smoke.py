"""Drive Celeris's device paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: train, engine, kernels, serve
    python chip_smoke.py --chips 4   # four chips: coded / hierarchical
                                     # gradient sync against exact
    python chip_smoke.py --smoke     # the same phases at smoke widths (a
                                     # CPU rehearsal; still refuses to
                                     # report ok off the chip)

One chip runs four phases at published widths, each through the entry
point a user calls:

- ``train``: ``Trainer`` on qwen2-0.5b (24 layers, d_model 896, vocab
  151936), seq 512 x global batch 8, five steps in ``exact`` and five in
  ``lossy_hadamard`` mode (the loss on the step-0 batch must fall),
  plus a zero-drop step from the same state in both modes, whose
  results must agree (coding is the identity there); every coded leaf
  must sync through the ``coded_roundtrip`` kernel (``coded_sync_paths``);
- ``engine``: a fig6-style 1024-node, 4-pod per-rail cell with the
  per-phase window, all four designs, through ``sweep(backend="jax")``,
  checked against ``backend="numpy"`` (rtol 1e-5);
- ``kernels``: the coded sync's Pallas kernel (``coded_roundtrip``),
  compiled, against ``repro.kernels.ref`` in bf16 and f32;
- ``serve``: prefill plus 16 greedy tokens from KV caches shipped
  through the coded lossy wire at delivered fraction 0.9.

Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
and is printed only when every phase passed and JAX's first device is a
TPU; otherwise the script exits nonzero.  Everything runs in this one
process, which holds the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import traceback

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.configs as C  # noqa: E402
from repro import sharding as shd  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.core.transport import (BatchedSimParams, NetworkParams,  # noqa: E402
                                  SimParams, coupling, designs, sweep,
                                  topology)
from repro.data.pipeline import DataConfig, make_source  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import mesh as mesh_mod  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.optim.adamw import OptConfig  # noqa: E402
from repro.serve import serve_step  # noqa: E402
from repro.train import sharding_rules as rules  # noqa: E402
from repro.train import train_step as ts  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

# full rate from step 1, so five steps move the loss on a fixed batch;
# at lr 1e-3 the 24-layer qwen2-0.5b step diverges by step 5
OPT = OptConfig(lr=1e-4, warmup_steps=1, total_steps=100)
# zero-drop agreement: relative L2 per gradient leaf (see agreement())
GRAD_RTOL = 2.0 ** -3
# bf16 keeps 8 significant bits: one ulp is 2**-7 of the value
BF16_ULP = 2.0 ** -7


def _peak_bytes(devices=None) -> list:
    """``peak_bytes_in_use`` per device (None where not reported)."""
    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


# ----------------------------------------------------------------------
# Zero-drop agreement rule (one chip and four chips)
# ----------------------------------------------------------------------

def _snapshot(state, metrics) -> dict:
    """Host copy of what one step from a fresh state produced: the
    updated params, AdamW's first moment (after step 1 it is
    ``(1 - b1) * clip * g``, the synced gradient) and the loss."""
    return {"mu": jax.device_get(state["opt"]["mu"]),
            "params": jax.device_get(state["params"]),
            "loss": float(metrics["loss"])}


def agreement(ref_snap: dict, snap: dict, lr: float) -> dict:
    """A zero-drop step against the reference step, each difference
    beside its tolerance.

    - first moment, i.e. the synced gradient: per leaf, relative L2
      difference <= 2**-3.  Gradients are bf16, and every sync path
      rounds shard partials and partial sums to bf16 (2**-8 of the
      partial each, which exceeds the final value where shards cancel),
      so two correct paths differ by bf16 noise of a few 1e-2; a wrong
      scale, sign, row mask or permutation moves a leaf by order 1.  A
      leaf whose exact gradient is itself rounding noise (the K bias:
      softmax ignores a shift shared by all keys) is measured against
      the norm it would have at the whole gradient's rms instead;
    - params: AdamW's first step is ``lr * g / (|g| + eps)``, a sign
      update, which flips where g is within rounding of 0: at most
      ``2 lr``, plus one bf16 ulp of the leaf's largest param from the
      bf16 cast of the f32 master;
    - loss: same forward, summed in another order: 1e-3 relative.
    """
    out = {"loss_diff": abs(snap["loss"] - ref_snap["loss"])}
    ok = out["loss_diff"] <= 1e-3 * abs(ref_snap["loss"])
    paths = jax.tree_util.tree_flatten_with_path(ref_snap["mu"])[0]
    leaves = [np.asarray(a, np.float64) for _, a in paths]
    rms = math.sqrt(sum(float(np.sum(a * a)) for a in leaves)
                    / max(sum(a.size for a in leaves), 1))
    worst, where = 0.0, ""
    for (path, _), a, b in zip(paths, leaves, jax.tree.leaves(snap["mu"])):
        b = np.asarray(b, np.float64)
        scale = max(float(np.linalg.norm(a)), rms * math.sqrt(a.size), 1e-30)
        rel = float(np.linalg.norm(a - b)) / scale
        if rel >= worst:
            worst, where = rel, jax.tree_util.keystr(path)
    out.update(grad_rel_l2=worst, grad_rel_l2_leaf=where,
               grad_rel_l2_tol=GRAD_RTOL)
    ok = ok and worst <= GRAD_RTOL
    worst, worst_tol, worst_ratio = 0.0, 0.0, 0.0
    for a, b in zip(jax.tree.leaves(ref_snap["params"]),
                    jax.tree.leaves(snap["params"])):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        d = float(np.max(np.abs(a - b))) if a.size else 0.0
        tol = 2.0 * lr + BF16_ULP * float(np.max(np.abs(a)))
        if d / tol >= worst_ratio:
            worst, worst_tol, worst_ratio = d, tol, d / tol
    out.update(params_max_diff=worst, params_tol=worst_tol)
    out["match"] = bool(ok and worst_ratio <= 1.0)
    return out


# ----------------------------------------------------------------------
# Phases
# ----------------------------------------------------------------------

def train_phase(cfg: ModelConfig, *, seq: int, batch: int, steps: int = 5,
                seed: int = 0) -> dict:
    """Trainer in ``exact`` and ``lossy_hadamard`` mode, no mesh."""
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch, seed=seed)
    res = {"arch": cfg.name, "seq": seq, "global_batch": batch,
           "ln_vocab": math.log(cfg.vocab_size)}
    snaps = {}
    ok = True
    # Per-step losses are on fresh batches; at a 151936-token vocabulary
    # their batch-to-batch spread hides five steps of learning.  That
    # the loss falls is read on one fixed batch: the step-0 batch,
    # before and after the steps (a batch never trained on, too).
    eval_loss = jax.jit(lambda p, b: M.lm_loss(p, cfg, b)[0])
    for mode in ("exact", "lossy_hadamard"):
        tr = Trainer(cfg, data_cfg=data, opt_cfg=OPT,
                     celeris=ts.CelerisConfig(mode=mode), seed=seed)
        init_key = jax.random.fold_in(tr.key, 0)   # Trainer's own init
        b0 = {k: jnp.asarray(v) for k, v in tr.source.global_batch(0).items()}
        # zero-drop step from the initial state (also the compile)
        st1, m1 = tr.step_fn(tr.state, b0, jax.random.fold_in(tr.key, 0),
                             jnp.float32(0.0))
        snaps[mode] = _snapshot(st1, m1)
        del st1, m1
        tr.state = jax.jit(ts.init_state, static_argnums=1)(init_key, cfg)
        held = {k: jnp.asarray(v)
                for k, v in tr.source.global_batch(10_000).items()}
        before = [float(eval_loss(tr.state["params"], x)) for x in (b0, held)]
        hist = tr.run(steps)
        after = [float(eval_loss(tr.state["params"], x)) for x in (b0, held)]
        loss = [float(x) for x in hist["loss"]]
        falls = bool(np.all(np.isfinite(loss + before + after))
                     and after[0] < before[0])
        near = abs(loss[0] - res["ln_vocab"]) < 2.0
        res[mode] = {"loss": loss, "drop_rate": hist["drop_rate"],
                     "step0_batch_loss_before_after": [before[0], after[0]],
                     "heldout_batch_loss_before_after": [before[1], after[1]],
                     "finite_and_falling": falls,
                     "step0_near_ln_vocab": near,
                     "peak_bytes_in_use": _peak_bytes()[0]}
        if mode == "lossy_hadamard":
            # one device: every coded leaf of this model takes the kernel
            paths = ts.coded_sync_paths(tr.state["params"], tr.celeris, None)
            res[mode]["coded_sync_paths"] = paths
            ok = ok and paths["fused"] > 0 and paths["xla"] == 0
        ok = ok and falls and near
        del tr
        gc.collect()
    res["zero_drop_exact_vs_coded"] = agreement(
        snaps["exact"], snaps["lossy_hadamard"], OPT.lr)
    res["ok"] = bool(ok and res["zero_drop_exact_vs_coded"]["match"])
    return res


def engine_phase(n_nodes: int, *, n_pods: int = 4, n_rounds: int = 20,
                 seeds=(0, 1), base: SimParams = SimParams(),
                 timeout_scale: float | None = None) -> dict:
    """One fig6-style cell (per-rail schedule, per-phase window, DCI
    oversubscription 8, every design) through ``sweep`` on the jax
    backend, against the numpy backend in the same process."""
    if timeout_scale is None:
        from benchmarks.budgets import TAIL_SCALE as timeout_scale
    grid = dict(n_nodes=(n_nodes,), seeds=tuple(seeds), n_pods=(n_pods,),
                schedules=("perrail",), windows=("phase",),
                designs=designs.DESIGNS, n_rounds=n_rounds,
                timeout_scale=timeout_scale,
                base=topology.hier_params(n_pods, base=base,
                                          dci_oversubscription=8.0))
    res_np = sweep(BatchedSimParams(**grid))
    res_j = sweep(BatchedSimParams(backend="jax", **grid))
    res_j2 = sweep(BatchedSimParams(backend="jax", **grid))
    worst = {"p99": 0.0, "recv_frac": 0.0, "tier_recv_frac": 0.0}
    ok = res_j.stats.keys() == res_np.stats.keys()
    for k, a in res_np.stats.items():
        for b in (res_j.stats[k], res_j2.stats[k]):
            for name in worst:
                x = np.asarray(getattr(a, name), np.float64)
                y = np.asarray(getattr(b, name), np.float64)
                rel = np.abs(y - x) / np.maximum(np.abs(x), 1e-300)
                rel = np.where(np.abs(y - x) <= 1e-9, 0.0, rel)
                worst[name] = max(worst[name], float(np.max(rel)))
    ok = ok and all(v <= 1e-5 for v in worst.values())
    cel = [st for k, st in res_j.stats.items() if k[0] == "celeris"]
    return {"n_nodes": n_nodes, "n_pods": n_pods, "n_rounds": n_rounds,
            "seeds": list(seeds), "designs": list(designs.DESIGNS),
            "max_rel_diff_vs_numpy": worst, "rtol": 1e-5,
            "celeris_p99_ms": [st.p99 / 1e3 for st in cel],
            "celeris_loss": [st.mean_loss for st in cel], "ok": bool(ok)}


def kernels_phase(shapes=((256, 4096), (8192, 4096)), seed: int = 0) -> dict:
    """The coded sync's Pallas kernel, ``ops.coded_roundtrip``, against
    its ``ref`` oracle in bf16 and f32; off the CPU it must lower to
    ``tpu_custom_call``."""
    compiled = jax.default_backend() != "cpu"
    out = {"compiled": compiled, "shapes": [list(s) for s in shapes]}
    ok = True
    for rows, n in shapes:
        key = jax.random.PRNGKey(seed + rows + n)
        signs = jax.random.rademacher(jax.random.fold_in(key, 1), (n,),
                                      dtype=jnp.float32)
        mask = jax.random.uniform(jax.random.fold_in(key, 4), (n,)) >= 0.1
        colscale = mask * (n / jnp.sum(mask))
        for dtype in (jnp.bfloat16, jnp.float32):
            x = jax.random.normal(key, (rows, n), dtype)
            args = (x, signs, colscale)
            want = jax.jit(ref.coded_roundtrip)(*args).astype(jnp.float32)
            jf = jax.jit(ops.coded_roundtrip)
            custom = "tpu_custom_call" in jf.lower(*args).as_text()
            got = jax.block_until_ready(jf(*args)).astype(jnp.float32)
            err = float(jnp.max(jnp.abs(got - want)))
            # the f32 sums' order differs from the oracle's, so a bf16
            # result may round the other way: one ulp, <= 2^-7 of it
            cast = 2.0 ** -7 if dtype == jnp.bfloat16 else 0.0
            good = bool(jnp.all(jnp.abs(got - want)
                                <= 1e-5 * jnp.max(jnp.abs(want))
                                + cast * jnp.abs(want)))
            good = good and custom == compiled
            name = f"coded_roundtrip_{jnp.dtype(dtype).name}_{rows}x{n}"
            out[name] = {"max_abs_err": err, "tpu_custom_call": custom,
                         "ok": good}
            ok = ok and good
    out["ok"] = bool(ok)
    return out


def serve_phase(cfg: ModelConfig, *, batch: int = 4, prompt_len: int = 48,
                gen: int = 16, kv_frac: float = 0.9, n_rows: int = 64,
                seed: int = 0) -> dict:
    """Prefill + greedy decode from KV caches shipped through the coded
    lossy wire (``serve_step.degrade_caches``, jitted), as ``examples/
    serve_batched.py --kv-frac`` does."""
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab_size)
    prefill = serve_step.make_prefill(cfg, prompt_len + gen)
    logits, caches = prefill(params, {"tokens": prompt})
    first = jnp.argmax(logits, -1)[:, None]
    clean = jax.tree.map(jnp.copy, caches)
    mask = jnp.asarray(coupling.kv_hole_masks(np.array([kv_frac]), n_rows,
                                              seed=seed)[0])
    deg = jax.jit(serve_step.degrade_caches)(caches, mask,
                                             jax.random.PRNGKey(seed + 2))
    toks = serve_step.greedy_decode(cfg, params, deg, first, prompt_len, gen)
    toks_clean = serve_step.greedy_decode(cfg, params, clean, first,
                                          prompt_len, gen)
    t = np.asarray(toks)
    ok = (t.shape == (batch, gen) and bool(np.all(t >= 0))
          and bool(np.all(t < cfg.vocab_size))
          and bool(jnp.all(jnp.isfinite(logits))))
    return {"arch": cfg.name, "batch": batch, "prompt_len": prompt_len,
            "gen": gen, "kv_frac": kv_frac,
            "wire_rows_lost": int(n_rows - int(mask.sum())),
            "tokens_row0": t[0].tolist(),
            "agree_with_clean_kv": float(np.mean(t == np.asarray(toks_clean))),
            "ok": bool(ok)}


def multichip_phase(cfg: ModelConfig, *, seq: int, batch: int,
                    seed: int = 0) -> dict:
    """Zero-drop steps on four devices: coded ``lossy_hadamard`` on a
    (data=4, model=1) mesh and ``hierarchical`` on a 2-pod (2, 2, 1)
    mesh, each against ``exact`` on the same mesh, and exact on the
    mesh against exact on one device, all on one global batch."""
    src = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                 global_batch=batch, seed=seed))
    host = src.global_batch(0)
    key = jax.random.PRNGKey(seed)

    def one_step(mesh, mode):
        shd.set_global_mesh(mesh)
        step = ts.make_train_step(cfg, mesh, OPT,
                                  ts.CelerisConfig(mode=mode))
        init = lambda k: ts.init_state(k, cfg)   # noqa: E731
        k0 = jax.random.fold_in(key, 0)
        if mesh is None:
            state = jax.jit(init)(k0)
            b = {k: jnp.asarray(v) for k, v in host.items()}
        else:
            sh = ts.state_shardings(jax.eval_shape(init, k0), mesh)
            state = jax.jit(init, out_shardings=sh)(k0)
            specs = rules.batch_specs(mesh, host)
            b = {k: jax.device_put(v, jax.sharding.NamedSharding(
                mesh, specs[k])) for k, v in host.items()}
        st1, m1 = step(state, b, jax.random.fold_in(key, 1), jnp.float32(0.0))
        snap = _snapshot(st1, m1)
        del st1, state
        gc.collect()
        return snap

    meshes = {"data4": (lambda: shd.make_mesh((4, 1), ("data", "model")),
                        ("exact", "lossy_hadamard")),
              "pod2x2": (lambda: mesh_mod.make_pod_mesh(2, 2, 1),
                         ("exact", "hierarchical"))}
    out, ok, snaps = {}, True, {}
    for name, (make, modes) in meshes.items():
        mesh = make()
        for mode in modes:
            snaps[(name, mode)] = one_step(mesh, mode)
        coded = modes[1]
        cmp_ = agreement(snaps[(name, "exact")], snaps[(name, coded)],
                         OPT.lr)
        out[f"{name}_{coded}_vs_exact"] = cmp_
        ok = ok and cmp_["match"]
    out["peak_bytes_in_use_per_device"] = _peak_bytes(jax.devices()[:4])
    one = one_step(None, "exact")
    cmp_ = agreement(one, snaps[("data4", "exact")], OPT.lr)
    out["data4_exact_vs_one_device_exact"] = cmp_
    shd.set_global_mesh(None)
    out["ok"] = bool(ok and cmp_["match"])
    return out


def device_report() -> dict:
    """The closing line; refuses anything but a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"JAX's first device is {dev.platform!r}, not a "
                           "TPU: no result is reported")
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(jax.devices())}}


# ----------------------------------------------------------------------

def _phases(chips: int, smoke: bool):
    if smoke:
        cfg = C.get_smoke("qwen2-0.5b")
        seq, batch = 32, 8
    else:
        cfg = C.get("qwen2-0.5b")
        seq, batch = 512, 8
    if chips == 4:
        return [("multichip", lambda: multichip_phase(cfg, seq=seq,
                                                      batch=batch))]
    small = SimParams(net=NetworkParams(n_nodes=32, burst_on_prob=0.0008))
    return [
        ("train", lambda: train_phase(cfg, seq=seq, batch=batch)),
        ("engine", (lambda: engine_phase(32, n_pods=2, n_rounds=8,
                                         base=small)) if smoke
         else (lambda: engine_phase(1024))),
        ("kernels", (lambda: kernels_phase(((16, 1024),))) if smoke
         else kernels_phase),
        ("serve", (lambda: serve_phase(cfg, batch=2, prompt_len=8, gen=4))
         if smoke else (lambda: serve_phase(cfg))),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="smoke widths (CPU rehearsal); the closing "
                         "platform check still refuses a non-TPU device")
    args = ap.parse_args(argv)
    if not args.smoke and jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is "
              f"{jax.devices()[0].platform!r})", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" JAX sees {len(jax.devices())}", file=sys.stderr)
        return 2
    print(json.dumps({"compile_cache": compile_cache.enable(),
                      "device_kind": jax.devices()[0].device_kind,
                      "n_devices": len(jax.devices())}), flush=True)
    failed = []
    for name, run in _phases(args.chips, args.smoke):
        try:
            res = run()
        except Exception:   # noqa: BLE001 - report and go on to the next
            traceback.print_exc()
            res = {"ok": False, "error": traceback.format_exc(limit=3)}
        print(json.dumps({"phase": name, **res}, default=float), flush=True)
        if not res.get("ok"):
            failed.append(name)
        gc.collect()
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps(device_report()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
