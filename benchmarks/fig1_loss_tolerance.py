"""Paper Fig. 1: model quality stable under partial network drops (<=5%).

Trains the same smoke LM on the Markov corpus with Celeris lossy
gradient sync at several drop rates (Hadamard recovery on) and compares
final losses.  Paper claim: <=5% drop is within noise; heavy drop
degrades.
"""
import numpy as np

import repro.configs as C
from repro.data.pipeline import DataConfig
from repro.optim.adamw import OptConfig
from repro.train.train_step import CelerisConfig
from repro.train.trainer import Trainer, StragglerModel


class _FixedDrop(StragglerModel):
    def __init__(self, p):
        super().__init__()
        self.p = p

    def drop_rate(self, timeout, rng):
        return self.p


def run(steps=60, seed=0):
    cfg = C.get_smoke("qwen2-0.5b")
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8,
                    seed=1)
    rows = []
    print("\n== Fig. 1: training quality vs drop rate (Hadamard on) ==")
    finals = {}
    for drop in (0.0, 0.01, 0.05, 0.20):
        tr = Trainer(cfg, data_cfg=dc,
                     opt_cfg=OptConfig(lr=1e-3, warmup_steps=10,
                                       total_steps=500),
                     celeris=CelerisConfig(
                         mode="lossy_hadamard" if drop > 0 else "exact",
                         min_coded_size=1024),
                     seed=seed, straggler=_FixedDrop(drop))
        h = tr.run(steps)
        final = float(np.mean(h["loss"][-10:]))
        finals[drop] = final
        print(f"drop={drop*100:5.1f}%  final loss {final:.4f}  "
              f"recv_frac {np.mean(h['recv_frac'][-10:]):.3f}")
        rows.append((f"fig1_final_loss_drop{int(drop*100)}",
                     round(final, 4), None))
    delta5 = finals[0.05] - finals[0.0]
    print(f"delta(5% vs lossless) = {delta5:+.4f}  "
          f"(paper: stable under <=5% drops)")
    rows.append(("fig1_delta_loss_at_5pct", round(delta5, 4), 0.0))
    return rows
