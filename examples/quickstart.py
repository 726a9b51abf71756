"""Quickstart: build an assigned arch (reduced config), train a few
steps on the synthetic Markov corpus, then greedy-generate.

    PYTHONPATH=src python examples/quickstart.py --arch gemma2-9b
"""
import argparse

import jax.numpy as jnp

import repro.configs as C
from repro.data.pipeline import DataConfig
from repro.optim.adamw import OptConfig
from repro.serve.serve_step import greedy_generate
from repro.train.trainer import Trainer
from repro.train.train_step import CelerisConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b",
                    help="any assigned arch id (dashes or underscores)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--celeris", action="store_true",
                    help="lossy (best-effort) gradient sync")
    args = ap.parse_args()

    cfg = C.get_smoke(args.arch)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"pattern={cfg.block_pattern}")

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
    tr = Trainer(cfg, data_cfg=dc,
                 opt_cfg=OptConfig(lr=1e-3, warmup_steps=10,
                                   total_steps=args.steps * 2),
                 celeris=CelerisConfig(
                     mode="lossy_hadamard" if args.celeris else "exact",
                     min_coded_size=1024))
    hist = tr.run(args.steps, on_metrics=lambda s, m: print(
        f"step {s:3d} loss {m['loss']:.4f} recv {m['recv_frac']:.3f} "
        f"({m['wall_s']:.2f}s)"))
    print(f"loss: {hist['loss'][0]:.3f} -> {hist['loss'][-1]:.3f}")

    if cfg.frontend is None and not cfg.is_encdec:
        prompt = jnp.zeros((2, 8), jnp.int32)
        out = greedy_generate(cfg, tr.state["params"], prompt, n_steps=12)
        print("greedy sample token ids:", out[0].tolist())


if __name__ == "__main__":
    main()
