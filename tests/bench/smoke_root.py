"""A benchmark root at smoke widths, for CPU rehearsals of the harness.

``make(tmp)`` copies ``bench/`` into ``tmp`` and writes a
``BENCHMARK.json`` whose cells run the train traffic at qwen2's smoke
widths (2 layers, d_model 128, vocab 512), with limits of their own
(``SMOKE_LIMITS``).
"""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SMOKE_CONFIG = {
    "num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 7,
    "num_key_value_heads": 1, "intermediate_size": 256, "vocab_size": 512,
    "rope_theta": 10000.0, "registry": "qwen2-0.5b:smoke",
}
SMOKE_TRAFFIC = {"seq_len": 32, "trace_seconds": 1}
# Smoke widths read differently from the cells': sound runs on the CPU
# read loss_gap <= 1.6e-5, grad_norm_gap <= 0.013 (a bias leaf of 128),
# update_norm_gap <= 0.005; the float8 control reads loss_gap 1.1e-4 to
# 3.7e-4, half a batch 1e-3 to 4e-3 (and grad_norm_gap >= 0.12).
SMOKE_LIMITS = {"loss_gap": 5e-5, "grad_norm_gap": 0.05,
                "update_norm_gap": 0.05}


def make(tmp: str, cells=("coded", "exact")) -> str:
    man = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.load(open(os.path.join(REPO, "bench/configs/qwen2-0.5b.json")))
    cfg.update(SMOKE_CONFIG, name="qwen2-smoke")
    with open(os.path.join(tmp, "bench/configs/qwen2-smoke.json"), "w") as f:
        json.dump(cfg, f)
    wls = []
    for traffic in cells:
        bench = os.path.join(tmp, "bench")
        t = json.load(open(os.path.join(bench, f"traffic/{traffic}.json")))
        t.update(SMOKE_TRAFFIC)
        with open(os.path.join(bench, f"traffic/smoke-{traffic}.json"),
                  "w") as f:
            json.dump(t, f)
        with open(os.path.join(bench, f"limits/qwen2-smoke.{traffic}.json"),
                  "w") as f:
            json.dump(SMOKE_LIMITS, f)
        wls.append({"name": f"qwen2-smoke.{traffic}", "config": "qwen2-smoke",
                    "traffic": f"smoke-{traffic}", "chips": 1,
                    "why": "smoke"})
    man["configs"] = [{"name": "qwen2-smoke", "source": "smoke",
                       "file": "bench/configs/qwen2-smoke.json",
                       "reduced": [], "why": "smoke"}]
    man["workloads"] = wls
    names = [w["name"] for w in wls]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = names
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return tmp

