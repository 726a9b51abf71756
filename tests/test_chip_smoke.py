"""chip_smoke.py's phases at smoke widths on the CPU: every phase's own
checks pass, and the closing platform check refuses the CPU."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:                 # chip_smoke.py sits at the root
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import repro.configs as C  # noqa: E402
from repro.core.transport import NetworkParams, SimParams  # noqa: E402


def test_chip_smoke_phases_pass_at_smoke_width_and_refuse_cpu():
    cfg = C.get_smoke("qwen2-0.5b")
    train = chip_smoke.train_phase(cfg, seq=16, batch=4, steps=3)
    assert train["ok"], train
    assert train["zero_drop_exact_vs_coded"]["match"], train

    small = SimParams(net=NetworkParams(n_nodes=32, burst_on_prob=0.0008))
    engine = chip_smoke.engine_phase(32, n_pods=2, n_rounds=6, base=small)
    assert engine["ok"], engine

    kernels = chip_smoke.kernels_phase(((8, 256),))
    assert kernels["ok"] and not kernels["compiled"], kernels

    serve = chip_smoke.serve_phase(cfg, batch=2, prompt_len=8, gen=3)
    assert serve["ok"], serve

    with pytest.raises(RuntimeError, match="not a TPU"):
        chip_smoke.device_report()
    assert chip_smoke.main([]) == 2      # no TPU: refuses before any phase
