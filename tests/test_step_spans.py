"""The train step's layer scopes and the Trainer's host spans.

- The lowered step carries one ``jax.named_scope`` per layer in its HLO
  metadata, and every op that computes from the step's inputs lies
  under one of them (the step counter's increment aside).
- The scopes change metadata only: three steps with and without them
  give bit-identical losses and state.
- A profiler trace of ``Trainer.run`` holds each ``trainer.*`` span once
  per step, nested in that step's ``trainer.step`` and tagged with its
  number.
"""
import collections
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.data.pipeline import DataConfig, make_source
from repro.optim import adamw
from repro.optim.adamw import OptConfig
from repro.train import train_step as ts
from repro.train.trainer import Trainer

SCOPES = ("fwd_bwd", "grad_sync", "optimizer")
# one device: each coded leaf is one kernel under ``roundtrip``; the
# sign draw stays under ``encode``, the mask under ``mask``
SYNC_CHILDREN = ("encode", "mask", "roundtrip")
HOST_PHASES = ("trainer.batch", "trainer.drop", "trainer.dispatch",
               "trainer.read_metrics", "trainer.controller")
# ops that need no scope: they carry no data
_STRUCTURAL = ("parameter", "constant", "tuple", "get-tuple-element")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+) ([\w\-]+)\((.*)$")
_CALLEES = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")


def _cfg():
    return C.get_smoke("qwen2-0.5b")


def _batches(cfg, n):
    src = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                 global_batch=4, seed=3))
    return [{k: jnp.asarray(v) for k, v in src.global_batch(t).items()}
            for t in range(n)]


def _step(cfg, mode):
    return ts.make_train_step(
        cfg, None, OptConfig(lr=1e-3, warmup_steps=1),
        ts.CelerisConfig(mode=mode, n_rot=256, min_coded_size=1024),
        donate=False)


def _lowered_hlo(step, cfg) -> str:
    shapes = jax.eval_shape(lambda k: ts.init_state(k, cfg),
                            jax.random.PRNGKey(0))
    return step.lower(shapes, _batches(cfg, 1)[0], jax.random.PRNGKey(0),
                      jnp.float32(0.2)).as_text(dialect="hlo",
                                                debug_info=True)


def _scope(op_name: str):
    return next((p for p in op_name.split("/") if p in SCOPES), None)


def _unscoped_ops(hlo: str) -> list:
    """``(opcode, op_name, reads_an_input)`` of every op under no scope.

    An op in a called computation (a loop body, a call, a reducer) is
    under the scope of the op that calls it; ``reads_an_input`` is
    whether the op depends on a parameter of the step."""
    comps, entry, cur = collections.OrderedDict(), None, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            cur = cur.lstrip("%")
            comps[cur] = []
            if line.startswith("ENTRY"):
                entry = cur
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            on = re.search(r'op_name="([^"]*)"', line)
            operands = re.findall(r"%?([\w.\-]+)", m.group(4).split(")")[0])
            comps[cur].append((m.group(1), m.group(3),
                               on.group(1) if on else "", operands,
                               _CALLEES.findall(line)))
    reads = set()
    inherited, todo, out = {entry: None}, [entry], []
    while todo:
        c = todo.pop()
        for name, opcode, op_name, operands, callees in comps[c]:
            if c == entry and (opcode == "parameter"
                               or any(o in reads for o in operands)):
                reads.add(name)
            scope = _scope(op_name) or inherited[c]
            for callee in callees:
                if callee not in inherited:
                    inherited[callee] = scope
                    todo.append(callee)
            if scope is None and opcode not in _STRUCTURAL:
                out.append((opcode, op_name, name in reads))
    return out


@pytest.mark.parametrize("mode", ["exact", "lossy_hadamard"])
def test_lowered_step_carries_layer_scopes(mode):
    cfg = _cfg()
    hlo = _lowered_hlo(_step(cfg, mode), cfg)
    names = re.findall(r'op_name="([^"]*)"', hlo)
    assert any("/fwd_bwd/" in n and "transpose(" in n for n in names)
    assert any("/optimizer/" in n for n in names)
    if mode == "lossy_hadamard":
        for child in SYNC_CHILDREN:
            assert any(f"/grad_sync/{child}/" in n for n in names), child
        assert not any("/grad_sync/decode/" in n for n in names)
    else:   # one device, exact: no sync op of its own
        assert not any("/grad_sync/" in n for n in names)
    # Outside every scope: constant tables JAX hoists out of the loss
    # (RoPE angles, the causal mask), which read no input, and the
    # step counter's increment.
    reading = [(opc, n) for opc, n, reads in _unscoped_ops(hlo) if reads]
    assert reading == [("add", "jit(train_step)/add")], reading


MOE_CHILDREN = ("route", "dispatch", "experts", "combine")
# an instruction's name and opcode; a sort's shape is a tuple
_OPCODE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (?:\([^)]*\)|\S+) ([\w\-]+)\(")


@pytest.fixture(scope="module")
def granite_hlo():
    """The compiled smoke Granite train step (coded sync) as text."""
    cfg = C.get_smoke("granite-moe-3b-a800m")
    shapes = jax.eval_shape(lambda k: ts.init_state(k, cfg),
                            jax.random.PRNGKey(0))
    return _step(cfg, "lossy_hadamard").lower(
        shapes, _batches(cfg, 1)[0], jax.random.PRNGKey(0),
        jnp.float32(0.2)).compile().as_text()


def test_moe_scope_and_children_in_granite_step(granite_hlo):
    """Granite's MoE block runs under ``moe`` with its four children,
    inside ``fwd_bwd``, forward and backward, in the compiled step's
    op_names; qwen2's step has no ``moe`` op."""
    names = re.findall(r'op_name="([^"]*)"', granite_hlo)
    for child in MOE_CHILDREN:
        mine = [n for n in names if f"/moe/{child}/" in n
                and "/fwd_bwd/" in n]
        assert any("transpose(" not in n for n in mine), child
        assert any("transpose(" in n for n in mine), child
    qwen = _cfg()
    assert not any("moe/" in n for n in re.findall(
        r'op_name="([^"]*)"', _lowered_hlo(_step(qwen, "exact"), qwen)))


def test_moe_permutes_gather_and_never_scatter(granite_hlo):
    """The dropless block's dispatch and combine run no scatter, forward,
    recomputed forward or backward: their backward is the inverse
    gather, and their indices come from sorts and a one-hot count.
    Both children gather, in the forward and under ``transpose(``."""
    ops = collections.defaultdict(list)
    for line in granite_hlo.splitlines():
        m, on = _OPCODE.match(line), re.search(r'op_name="([^"]*)"', line)
        if m and on:
            child = next((c for c in ("dispatch", "combine")
                          if f"/moe/{c}/" in on.group(1)), None)
            if child:
                ops[(child, m.group(1))].append(on.group(1))
    for child in ("dispatch", "combine"):
        assert not ops[(child, "scatter")], ops[(child, "scatter")]
        gathers = ops[(child, "gather")]
        assert any("transpose(" not in n for n in gathers), child
        assert any("transpose(" in n for n in gathers), child


def _no_scope(monkeypatch):
    class NoScope(contextlib.ContextDecorator):
        def __init__(self, name):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax, "named_scope", NoScope)
    monkeypatch.setattr(adamw, "apply_updates",
                        adamw.apply_updates.__wrapped__)


@pytest.mark.parametrize("mode", ["exact", "lossy_hadamard"])
def test_scopes_leave_results_bit_identical(mode, monkeypatch):
    cfg = _cfg()
    batches = _batches(cfg, 3)
    key = jax.random.PRNGKey(0)

    def three_steps(step):
        state = ts.init_state(key, cfg)
        losses = []
        for t, b in enumerate(batches):
            state, m = step(state, b, jax.random.fold_in(key, t),
                            jnp.float32(0.2))
            losses.append(np.asarray(m["loss"]))
        return losses, jax.tree.leaves(state)

    scoped = _step(cfg, mode)
    got = three_steps(scoped)
    with monkeypatch.context() as mp:
        _no_scope(mp)
        plain = _step(cfg, mode)
        assert not any(s in _lowered_hlo(plain, cfg) for s in SCOPES)
        want = three_steps(plain)
    for a, b in zip(got[0] + got[1], want[0] + want[1]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _host_spans(log_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("trainer."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats).get("step")))
    return out


def test_trainer_spans_nest_in_their_step(tmp_path):
    cfg = _cfg()
    tr = Trainer(cfg, data_cfg=DataConfig(vocab_size=cfg.vocab_size,
                                          seq_len=16, global_batch=4,
                                          seed=1),
                 celeris=ts.CelerisConfig(mode="lossy_hadamard"),
                 ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2)
    tr.run(1)                                  # compiles outside the trace
    with jax.profiler.trace(str(tmp_path / "trace")):
        tr.run(2)                              # steps 1 and 2; 1 saves
    spans = _host_spans(str(tmp_path / "trace"))
    steps = {s: (a, b) for n, a, b, s in spans if n == "trainer.step"}
    assert sorted(steps) == [1, 2]
    count = collections.Counter((n, s) for n, _, _, s in spans)
    for step in (1, 2):
        for phase in HOST_PHASES:
            assert count[(phase, step)] == 1, (phase, step, count)
    assert count[("trainer.step", 1)] == count[("trainer.step", 2)] == 1
    assert count[("trainer.checkpoint", 1)] == 1
    assert count[("trainer.checkpoint", 2)] == 0
    for name, a, b, step in spans:
        lo, hi = steps[step]
        assert lo <= a and b <= hi, (name, step)
    # the phases follow one another in the order the loop runs them
    for step in (1, 2):
        starts = [next(a for n, a, _, s in spans if n == p and s == step)
                  for p in HOST_PHASES]
        assert starts == sorted(starts)
