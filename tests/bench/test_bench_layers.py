"""The reduction of a trace by layer scope and host span, on a synthetic
event list and on a trace recorded here."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import layers as L  # noqa: E402

MS = 1_000_000
BWD = "jit(train_step)/fwd_bwd/transpose(jvp())/while"


def _trace():
    # window 0..100 ms.  Device 0: a backward loop (10-50) around two
    # body ops, the coded sync's encode and decode overlapping by 2 ms,
    # the optimizer, an op of no scope, and one op past the window.
    dev0 = [("while.1", 10 * MS, 50 * MS, BWD),
            ("fusion.2", 12 * MS, 20 * MS, BWD + "/body/closed_call/dot"),
            ("fusion.3", 25 * MS, 45 * MS,
             "jit(train_step)/fwd_bwd/jvp()/while/body/dot"),
            ("fusion.4", 55 * MS, 70 * MS,
             "jit(train_step)/grad_sync/encode/mul"),
            ("fusion.5", 68 * MS, 75 * MS,
             "jit(train_step)/grad_sync/decode/mul"),
            ("fusion.6", 80 * MS, 90 * MS, "jit(train_step)/optimizer/add"),
            ("copy.7", 90 * MS, 92 * MS, ""),
            ("fusion.6", 130 * MS, 140 * MS,
             "jit(train_step)/optimizer/add")]
    spans = [("bench.window", 0, 100 * MS, None),
             ("trainer.step", 0, 50 * MS, 0),
             ("trainer.batch", 0, 5 * MS, 0),
             ("trainer.dispatch", 5 * MS, 10 * MS, 0),
             ("trainer.read_metrics", 10 * MS, 48 * MS, 0),
             ("trainer.controller", 48 * MS, 50 * MS, 0),
             ("trainer.step", 50 * MS, 97 * MS, 1),
             ("trainer.batch", 50 * MS, 53 * MS, 1),
             ("trainer.read_metrics", 53 * MS, 95 * MS, 1)]
    return {"devices": {0: dev0}, "spans": spans}


def test_self_time_by_scope_counts_nested_ops_once():
    r = L.reduce(_trace(), [0])
    assert r["window_s"] == pytest.approx(0.1)
    assert r["steps"] == 2
    # busy: 10-50, 55-75, 80-92
    assert r["busy_s"] == pytest.approx(0.072)
    s = r["scopes_s"]
    # the loop's own time is 10-12, 20-25, 45-50: 12 ms besides its body
    assert s["fwd_bwd"] == pytest.approx(0.040)
    assert s["grad_sync"] == pytest.approx(0.020)
    assert s["optimizer"] == pytest.approx(0.010)
    assert s["unscoped"] == pytest.approx(0.002)
    assert sum(s.values()) == pytest.approx(r["busy_s"])
    c = r["children_s"]
    assert c["fwd_bwd/backward"] == pytest.approx(0.020)
    assert c["fwd_bwd/forward"] == pytest.approx(0.020)
    # 68-70: the op that started last (decode) takes the overlap
    assert c["grad_sync/encode"] == pytest.approx(0.013)
    assert c["grad_sync/decode"] == pytest.approx(0.007)


def test_idle_split_by_innermost_host_span():
    r = L.reduce(_trace(), [0])
    i = r["idle_s"]
    # idle 0-10, 50-55, 75-80, 92-100
    assert i["trainer.batch"] == pytest.approx(0.008)
    assert i["trainer.dispatch"] == pytest.approx(0.005)
    assert i["trainer.read_metrics"] == pytest.approx(0.010)
    assert i["trainer.step"] == pytest.approx(0.002)      # 95-97
    assert i["outside_steps"] == pytest.approx(0.003)     # 97-100
    assert sum(i.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_devices_averaged_and_per_step():
    t = _trace()
    t["devices"][1] = [("fusion.9", 0, 100 * MS, "")]
    r = L.reduce(t, [0, 1])
    assert r["busy_s"] == pytest.approx((0.072 + 0.1) / 2)
    assert r["scopes_s"]["unscoped"] == pytest.approx((0.002 + 0.1) / 2)
    ms = L.per_step_ms(L.reduce(_trace(), [0]))
    assert ms["scopes_ms"]["fwd_bwd"] == pytest.approx(20.0)
    assert ms["idle_ms"]["trainer.read_metrics"] == pytest.approx(5.0)


def test_window_from_steps_and_no_ops():
    t = _trace()
    t["spans"] = [s for s in t["spans"] if s[0] != "bench.window"]
    assert L.window(t) == (0, 97 * MS)
    t["devices"] = {}
    assert L.reduce(t, [0]) is None


def test_layer_of_and_op_names_from_hlo():
    assert L.layer_of(BWD) == ("fwd_bwd", "backward")
    assert L.layer_of("jit(s)/grad_sync/psum/psum") == ("grad_sync", "psum")
    assert L.layer_of("jit(s)/optimizer/mul") == ("optimizer", None)
    assert L.layer_of("jit(s)/add") == ("unscoped", None)
    hlo = ('  %fusion.715 = bf16[8]{0} fusion(%p.1), kind=kLoop, '
           'calls=%fc.3, metadata={op_name="jit(s)/optimizer/mul" '
           'stack_frame_id=2}\n'
           '  ROOT %while.12 = (s32[]) while(%t), condition=%c, '
           'body=%body.1, metadata={op_name="' + BWD + '"}\n'
           '  %copy.3 = f32[8]{0} copy(%p.2)\n')
    module, names = L.op_names("HloModule jit_s, is_scheduled=true\n"
                               "%body.1 (p: s32[]) -> s32[] {\n"
                               "  %copy-done.2 = f32[8]{0} copy-done(%c)\n"
                               "}\n"
                               "ENTRY %main.3 (p.1: f32[8]) -> f32[8] {\n"
                               + hlo + "}\n")
    assert module == "jit_s"
    # the copy XLA put in the loop body takes the loop's op_name; the one
    # in the entry computation has none
    assert names == {"fusion.715": "jit(s)/optimizer/mul", "while.12": BWD,
                     "copy-done.2": BWD, "copy.3": ""}


def test_join_names_only_the_steps_own_operations():
    ops = [("fusion.1", 10, 20), ("fusion.1", 40, 45), ("copy.2", 60, 61)]
    modules = [(0, 30, "jit_train_step(123)"), (35, 50, "jit_fold_in(9)"),
               (55, 70, "jit_train_step(123)")]
    got = L.join(ops, modules, "jit_train_step",
                 {"fusion.1": "jit(s)/optimizer/mul", "copy.2": BWD})
    assert [o[3] for o in got] == ["jit(s)/optimizer/mul", "", BWD]


def test_recorded_spans_load(tmp_path):
    """A trace recorded here: the host spans and their steps are found;
    the CPU backend writes no device plane."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(2):
        with jax.profiler.TraceAnnotation("trainer.step", step=i):
            with jax.profiler.TraceAnnotation("trainer.dispatch", step=i):
                y = f(x)
            y.block_until_ready()
    jax.profiler.stop_trace()
    raw = L.load(L.trace_mod.xplane_path(str(tmp_path)))
    got = sorted((n, s) for n, _, _, s in raw["spans"])
    assert got == [("trainer.dispatch", 0), ("trainer.dispatch", 1),
                   ("trainer.step", 0), ("trainer.step", 1)]
    assert raw["devices"] == {}
