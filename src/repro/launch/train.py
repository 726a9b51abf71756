"""Training launcher.

Container-scale (real devices):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --smoke --steps 50 --celeris --ckpt-dir /tmp/run1

On a real TPU pod this same entrypoint runs under the production mesh
(--mesh single|multi picks 16x16 or 2x16x16); jax.distributed handles
multi-host process groups outside this container.
"""
import argparse

import jax

import repro.configs as C
from repro import sharding as shd
from repro.launch import compile_cache
from repro.data.pipeline import DataConfig
from repro.launch import mesh as mesh_mod
from repro.optim.adamw import OptConfig
from repro.train.trainer import Trainer
from repro.train.train_step import CelerisConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--celeris", action="store_true")
    ap.add_argument("--lossy-moe", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "host", "single", "multi"],
                    default="none")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = C.get_smoke(args.arch) if args.smoke else C.get(args.arch)
    mesh = None
    if args.mesh == "host":
        n = len(jax.devices())
        mesh = mesh_mod.make_host_mesh((max(n // 2, 1), min(2, n)))
    elif args.mesh == "single":
        mesh = mesh_mod.make_production_mesh()
    elif args.mesh == "multi":
        mesh = mesh_mod.make_production_mesh(multi_pod=True)
    if mesh is not None:
        shd.set_global_mesh(mesh)

    tr = Trainer(
        cfg,
        data_cfg=DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                            global_batch=args.global_batch),
        opt_cfg=OptConfig(lr=args.lr, total_steps=args.steps),
        celeris=CelerisConfig(
            mode="lossy_hadamard" if args.celeris else "exact",
            lossy_moe=args.lossy_moe),
        mesh=mesh, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    tr.run(args.steps, on_metrics=lambda s, m: print(
        f"step {s:4d} loss {m['loss']:.4f} nll {m['nll']:.4f} "
        f"recv {m['recv_frac']:.3f} lr {m['lr']:.2e} ({m['wall_s']:.2f}s)",
        flush=True))


if __name__ == "__main__":
    main()
