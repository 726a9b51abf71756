"""CPU rehearsals of every cell's traffic at smoke widths, through the
harness's own functions, with the look for a chip skipped; and the
command itself, which refuses a CPU."""
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(REPO, "src"), REPO, HERE]

import smoke_root  # noqa: E402
from bench import harness  # noqa: E402
from bench import run as R  # noqa: E402

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture(scope="module")
def train_root(tmp_path_factory):
    return smoke_root.make(str(tmp_path_factory.mktemp("train")))


def _line(root, workload, trace, seconds="1", seed="2147483701"):
    args = R.parse(["--workload", workload, "--seed", seed,
                    "--seconds", seconds, "--trace", str(trace)])
    out = R.run(args, require_tpu=False, t_start=time.perf_counter(),
                root=root)
    return json.loads(harness.result_line(**out))


@pytest.mark.parametrize("traffic", ["coded", "exact"])
def test_train_cell(train_root, traffic):
    wl = f"qwen2-smoke.{traffic}"
    line = _line(train_root, wl, 0)
    assert set(line) == KEYS
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s",
                                    "train_step_p90_ms", "setup_s"}
    assert set(line["checks"]) == {"loss_gap", "grad_norm_gap",
                                   "update_norm_gap"}
    assert line["device"]["count"] >= 1


def test_train_cell_traced(train_root):
    """With --trace 1 the line carries per-layer metrics only; the CPU
    writes no device plane, so the trace-read ones stay silent."""
    line = _line(train_root, "qwen2-smoke.coded", 1)
    assert KEYS <= set(line) <= KEYS | {"breakdown"}
    assert line["correct"] is True
    assert set(line["metrics"]) == {"trainer_host_ms"}


def test_watch_counts_gc_and_compiles_while_attached():
    import gc

    import jax

    from bench.runners import train
    watch = train._Watch()
    gc.collect()
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    watch.detach()
    assert watch.gc_s > 0 and watch.gc_longest <= watch.gc_s
    assert watch.events[watch.COMPILE] >= 1
    seen = dict(watch.events), watch.gc_s
    gc.collect()
    jax.jit(lambda x: x * 5 - 1)(jax.numpy.arange(7.0)).block_until_ready()
    assert (dict(watch.events), watch.gc_s) == seen


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.coded",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr
