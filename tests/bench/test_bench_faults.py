"""A run with the timed path broken underneath has to come out not
correct, and the control has to fail the limits: each fault that a
train cell can have is planted in the program's step, at smoke widths
on the CPU, with the harness's look for a chip skipped."""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(REPO, "src"), REPO, HERE]

import smoke_root  # noqa: E402
from bench import checks, control, harness  # noqa: E402
from bench import run as R  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke_root.make(str(tmp_path_factory.mktemp("faults")),
                           cells=("coded",))


def _broken(kind):
    """make_train_step with the step broken underneath."""
    import jax
    import jax.numpy as jnp

    from repro.train import train_step as ts
    real_make = ts.make_train_step

    def make(*a, **k):
        real = real_make(*a, **k)

        def step(state, batch, key, drop):
            if kind == "frozen":         # returns its state unchanged
                _, m = real(jax.tree.map(jnp.copy, state), batch, key, drop)
                return state, m
            # half of the batch left out, the mean taken over the rest
            half = {n: v[: v.shape[0] // 2] for n, v in batch.items()}
            return real(state, half, key, drop)
        return step
    return make


@pytest.mark.parametrize("kind", ["frozen", "half_batch"])
def test_broken_step_is_not_correct(root, kind, monkeypatch):
    from repro.train import train_step as ts
    monkeypatch.setattr(ts, "make_train_step", _broken(kind))
    args = R.parse(["--workload", "qwen2-smoke.coded", "--seed",
                    "2147483711", "--seconds", "1", "--trace", "0"])
    out = R.run(args, require_tpu=False, t_start=time.perf_counter(),
                root=root)
    assert out["correct"] is False, out["checks"]


def test_control_fails_the_limits(root):
    """The reference in float8 in the program's place, and half a batch
    left out, each fail at least one of the cell's limits."""
    man = harness.manifest(root)
    wl = harness.workload(man, "qwen2-smoke.coded")
    cfg = harness.config_file(man, wl["config"], root)
    traffic = harness.traffic_file(wl["traffic"], os.path.join(root,
                                                               "bench"))
    limits = harness.limits_file(wl["name"], os.path.join(root, "bench"))
    got = control.readings(cfg, traffic, 3, control.param_shapes(cfg),
                           faults=("control", "half_batch"))
    for fault, (numbers, _) in got.items():
        ok, chk = checks.judge(numbers, limits)
        assert not ok, (fault, chk)

