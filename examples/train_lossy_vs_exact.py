"""End-to-end training driver: lossless vs Celeris best-effort sync.

Trains the same model twice on the synthetic Markov corpus — once with
exact (RoCE-semantics) gradient AllReduce, once with Celeris lossy sync
(bounded windows -> drops -> Hadamard recovery), including a simulated
mid-run node failure + checkpoint restart on the Celeris run.

Container default is a ~15M model for CPU speed; pass ``--size 100m``
for the ~100M-parameter configuration (same code path, more compute):

    PYTHONPATH=src python examples/train_lossy_vs_exact.py \
        --size 100m --steps 300
"""
import argparse
import dataclasses
import shutil
import tempfile

import numpy as np

import repro.configs as C
from repro.configs.base import ModelConfig
from repro.data.pipeline import DataConfig
from repro.optim.adamw import OptConfig
from repro.train.trainer import Trainer, StragglerModel
from repro.train.train_step import CelerisConfig

SIZES = {
    # ~15M: CPU-quick;  ~100M: the e2e target (few hundred steps)
    "15m": dict(n_layers=4, d_model=256, n_heads=8, n_kv_heads=2, d_ff=1024),
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                 d_ff=2048),
}


def make_cfg(size: str) -> ModelConfig:
    return dataclasses.replace(
        C.get_smoke("qwen2-0.5b"), name=f"qwen2-style-{size}",
        vocab_size=8192, **SIZES[size])


def run_one(cfg, tag, steps, celeris, seed, ckpt_dir=None, fault_at=None):
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=128, global_batch=8,
                    seed=7)
    tr = Trainer(cfg, data_cfg=dc,
                 opt_cfg=OptConfig(lr=6e-4, warmup_steps=20,
                                   total_steps=steps),
                 celeris=celeris, seed=seed, ckpt_dir=ckpt_dir,
                 ckpt_every=25,
                 straggler=StragglerModel(burst_prob=0.15, burst_scale=2.5))
    try:
        h = tr.run(steps, simulate_fault_at=fault_at)
    except RuntimeError as e:
        print(f"[{tag}] {e} -> restarting from checkpoint")
        tr2 = Trainer(cfg, data_cfg=dc,
                      opt_cfg=OptConfig(lr=6e-4, warmup_steps=20,
                                        total_steps=steps),
                      celeris=celeris, seed=seed, ckpt_dir=ckpt_dir,
                      ckpt_every=25)
        h = tr2.run(steps - tr2.start_step)
    print(f"[{tag}] loss {h['loss'][0]:.4f} -> "
          f"{np.mean(h['loss'][-10:]):.4f} | mean recv_frac "
          f"{np.mean(h['recv_frac']):.3f} | mean drop "
          f"{np.mean(h['drop_rate'])*100:.1f}%")
    return h


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="15m", choices=list(SIZES))
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()

    cfg = make_cfg(args.size)
    print(f"model: {cfg.param_count()/1e6:.0f}M params, {args.steps} steps")

    h_exact = run_one(cfg, "exact  ", args.steps, CelerisConfig(), seed=0)

    tmp = tempfile.mkdtemp()
    try:
        h_lossy = run_one(
            cfg, "celeris", args.steps,
            CelerisConfig(mode="lossy_hadamard", min_coded_size=4096),
            seed=0, ckpt_dir=tmp, fault_at=min(args.steps - 10, 40))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    d = np.mean(h_lossy["loss"][-10:]) - np.mean(h_exact["loss"][-10:])
    print(f"\nfinal-loss delta (celeris - exact): {d:+.4f} "
          f"(paper Fig. 1: small drops are within noise)")


if __name__ == "__main__":
    main()
