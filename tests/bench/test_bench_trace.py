"""The benchmark's trace reduction on a synthetic event list and on a
trace recorded here."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace as T  # noqa: E402

MS = 1_000_000


def _trace():
    # window 0..100 ms; device 0 busy 10-30 (matmul), 25-40 (all-reduce),
    # 60-70 (fusion); device 1 busy 0-50
    dev0 = [("matmul.1", 10 * MS, 30 * MS),
            ("all-reduce.7", 25 * MS, 40 * MS),
            ("fusion.3", 60 * MS, 70 * MS),
            ("fusion.3", 120 * MS, 130 * MS)]        # outside the window
    dev1 = [("matmul.1", 0, 50 * MS)]
    host = [(T.WINDOW_SPAN, 0, 100 * MS),
            ("bench.step", 0, 50 * MS), ("bench.step", 50 * MS, 100 * MS),
            ("$pipeline.py:51 _gen", 41 * MS, 58 * MS)]
    return {"devices": {0: dev0, 1: dev1}, "host": host}


def test_busy_idle_and_collectives():
    r = T.reduce(_trace(), [0, 1])
    # device 0: union 10-40 + 60-70 = 40 ms; device 1: 50 ms
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s_per_device"] == pytest.approx([0.04, 0.05])
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["collective_s"] == pytest.approx(0.015)
    # 25-30 overlaps the matmul: 10 ms of the all-reduce run alone
    assert r["collective_exposed_s"] == pytest.approx(0.010)
    names = [n for n, _ in r["top_ops"]]
    assert names[0] == "matmul.1"
    assert dict(r["top_ops"])["matmul.1"] == pytest.approx((0.02 + 0.05) / 2)


def test_idle_gaps_named_by_host_activity():
    r = T.reduce(_trace(), [0])
    gaps = r["idle_gaps"]
    # device 0 idles 0-10, 40-60, 70-100 ms
    assert [g[1] for g in gaps] == pytest.approx([0.03, 0.02, 0.01])
    # 40-60 is covered mostly by the data generator, the shortest event
    # covering half of it
    assert gaps[1][0] == "$pipeline.py:51 _gen"
    assert gaps[0][0] == "bench.step"


def test_no_device_ops_reads_nothing():
    t = _trace()
    t["devices"] = {}
    assert T.reduce(t, [0]) is None


def test_union_and_subtract():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T._subtract([(0, 10)], [(2, 3), (5, 20)]) == 4


def test_recorded_trace_loads(tmp_path):
    """A trace recorded here: the window span and host events are found;
    the CPU backend writes no device plane, so nothing is reduced."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for i in range(2):
            with jax.profiler.StepTraceAnnotation("bench.step", step_num=i):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    raw = T.load(T.xplane_path(str(tmp_path)))
    lo, hi = T.window(raw)
    assert hi > lo
    assert any(n == "bench.step" for n, _, _ in raw["host"])
    assert T.reduce(raw, [0]) is None


def test_op_names_from_hlo_instructions():
    assert T.op_name("%fusion.464 = (bf16[8]{0}) fusion(%a), kind=kLoop") \
        == "fusion.464"
    assert T.op_name("%all-reduce.7 = f32[4]{0} all-reduce(%x)") \
        == "all-reduce.7"
    assert T.op_name("copy-start.3") == "copy-start.3"
    assert T.COLLECTIVE.match(T.op_name("%all-reduce-start.2 = f32[] a"))
