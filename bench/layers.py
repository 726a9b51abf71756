"""Split a profiler trace of training by the program's own layers.

The train step runs each layer under one ``jax.named_scope``
(``fwd_bwd``, ``grad_sync``, ``optimizer``; see
``src/repro/train/train_step.py``), which XLA keeps in every HLO op's
``op_name``; the ``Trainer`` runs each host phase of a step under one
``jax.profiler.TraceAnnotation`` (``trainer.*``, with the step number as
``step``), on the same clock as the device's operations.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists: per device, its operations as ``(name, start_ns, end_ns,
op_name)``, and the host's ``trainer.*`` and ``bench.*`` spans as
``(name, start_ns, end_ns, step)``.  A TPU trace names each operation by
its HLO instruction and carries no ``op_name``: ``op_names`` reads them
from the compiled step's HLO text.  ``reduce`` then works on those lists
alone, so it can be tested on a synthetic event list:

- each device operation's self time: its time minus what operations
  nested in it cover (a ``while`` and the operations of its body are
  counted once), summed by the outermost layer scope in its ``op_name``;
  what falls under none is ``unscoped``.  Together they are the busy
  time, the union of the operations' intervals;
- the device's idle time, split by the innermost ``trainer.*`` span that
  covers it; idle under none is ``outside_steps``.

Both are summed over the window (the benchmark's ``bench.window`` span,
else the extent of the ``trainer.step`` spans) and averaged over the
devices.  Run on a trace directory it prints the reduction per step::

    python bench/layers.py TRACE_DIR [--hlo STEP_HLO.txt]
"""
from __future__ import annotations

import heapq
import json
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as trace_mod  # noqa: E402

SCOPES = ("fwd_bwd", "grad_sync", "optimizer")
# the children of a scope that the reduction also reports
CHILDREN = {"grad_sync": ("encode", "mask", "decode", "psum")}
STEP_SPAN = "trainer.step"
SPAN_PREFIX = "trainer."
UNSCOPED = "unscoped"
OUTSIDE = "outside_steps"
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLEES = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def op_names(hlo_text: str) -> tuple:
    """``(module name, {instruction name: op_name})`` from an HLO
    module's text.  An instruction with no op_name of its own (a copy
    or an async wait XLA put in) takes that of the instruction that
    calls its computation: a loop body's, a fusion's."""
    module, comps, cur = "", {}, None
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
        elif line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = line.split()[1 if line.startswith("ENTRY ") else 0]
            cur = cur.lstrip("%")
            comps[cur] = []
        elif cur is not None:
            m = _HLO_INSTR.match(line)
            if m:
                on = _HLO_OP_NAME.search(line)
                callees = [c.strip().lstrip("%")
                           for one, many in _HLO_CALLEES.findall(line)
                           for c in (one or many).split(",")]
                comps[cur].append((m.group(1), on.group(1) if on else "",
                                   callees))
    inherited, out = {}, {}
    for comp in reversed(comps):       # callers print after their callees
        base = inherited.get(comp, "")
        for name, on, callees in comps[comp]:
            out[name] = on or base
            for c in callees:
                inherited.setdefault(c, out[name])
    return module, out


def load(path: str, hlo_text: str | None = None) -> dict:
    """``{"devices": {id: [(name, start, end, op_name)]}, "spans": [...]}``
    from an ``.xplane.pb`` file.  A TPU trace names each operation by its
    HLO instruction but does not carry its op_name: ``hlo_text``, the
    compiled step's HLO, gives it to the step's own operations (without
    it every operation is ``unscoped``)."""
    from jax.profiler import ProfileData
    module, names = op_names(hlo_text) if hlo_text else ("", {})
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = trace_mod._DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in (lines["XLA Modules"].events
                               if "XLA Modules" in lines else [])]
            ops = [(trace_mod.op_name(ev.name), ev.start_ns,
                    ev.start_ns + ev.duration_ns)
                   for ev in lines["XLA Ops"].events]
            devices[int(m.group(2))] = join(ops, mods, module, names)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith((SPAN_PREFIX,
                                           trace_mod.WINDOW_SPAN)):
                        step = dict(ev.stats).get("step")
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      None if step is None else int(step)))
    return {"devices": devices, "spans": spans}


def join(ops, modules, module: str, names: dict) -> list:
    """``(name, start, end, op_name)`` of each operation: ``names``
    applies to operations that run inside an execution of ``module``
    (``modules``: ``(start, end, "jit_train_step(<id>)")`` events); the
    operations of other programs (a ``fold_in``, a conversion) reuse
    instruction names and get none."""
    runs = sorted((s, e) for s, e, n in modules
                  if n.split("(", 1)[0] == module)
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(runs) and runs[j][1] <= s:
            j += 1
        inside = j < len(runs) and runs[j][0] <= s
        out.append((name, s, e, names.get(name, "") if inside else ""))
    return out


def layer_of(op_name: str) -> tuple:
    """``(scope, child)`` of an op_name: the outermost layer scope in its
    name stack (or ``unscoped``), and below it the child the reduction
    reports (``encode``/``mask``/``decode``/``psum`` under
    ``grad_sync``; ``backward`` or ``forward`` under ``fwd_bwd``)."""
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if p in SCOPES:
            rest = parts[i + 1:]
            if p == "fwd_bwd":
                back = any(r.startswith("transpose(") for r in rest)
                return p, "backward" if back else "forward"
            child = next((r for r in rest if r in CHILDREN.get(p, ())),
                         None)
            return p, child
    return UNSCOPED, None


def innermost(intervals) -> list:
    """``(start, end, label)`` pieces of the union of labelled
    intervals: each instant goes to the covering interval that started
    last (of two that start together, the shorter)."""
    evs = sorted(intervals, key=lambda x: (x[0], -x[1]))
    bounds = sorted({p for s, e, _ in evs for p in (s, e)})
    heap, i, out = [], 0, []
    for a, b in zip(bounds, bounds[1:]):
        while i < len(evs) and evs[i][0] <= a:
            s, e, lab = evs[i]
            heapq.heappush(heap, (-s, e - s, i, e, lab))
            i += 1
        while heap and heap[0][3] <= a:
            heapq.heappop(heap)
        if heap:
            if out and out[-1][1] == a and out[-1][2] == heap[0][4]:
                out[-1] = (out[-1][0], b, out[-1][2])
            else:
                out.append((a, b, heap[0][4]))
    return out


def _idle_by_span(gaps, pieces) -> dict:
    """Length of each gap split by the labelled ``pieces`` it overlaps;
    what no piece covers goes to ``outside_steps``."""
    out, j = {OUTSIDE: 0.0}, 0
    for s, e in gaps:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(pieces) and pieces[k][0] < e:
            a, b = max(s, pieces[k][0]), min(e, pieces[k][1])
            if b > a:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + (b - a)
                covered += b - a
            k += 1
        out[OUTSIDE] += (e - s) - covered
    return out


def window(trace: dict) -> tuple:
    """The benchmark's window span, else the extent of the steps."""
    for want in (trace_mod.WINDOW_SPAN, STEP_SPAN):
        spans = [(s, e) for n, s, e, _ in trace["spans"] if n == want]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
    raise ValueError(f"neither {trace_mod.WINDOW_SPAN!r} nor {STEP_SPAN!r} "
                     "spans in the trace")


def reduce(trace: dict, devices) -> dict | None:
    """Self time by layer scope and idle time by host span, in seconds
    over the window, averaged over ``devices`` (ids); None where the
    trace holds no operation of those devices."""
    lo, hi = window(trace)
    host = [(max(s, lo), min(e, hi), n) for n, s, e, _ in trace["spans"]
            if n.startswith(SPAN_PREFIX) and e > lo and s < hi]
    pieces = innermost(host)
    steps = sum(1 for n, s, e, _ in trace["spans"]
                if n == STEP_SPAN and lo <= (s + e) / 2 < hi)
    scopes, children, idle, busy, seen = {}, {}, {}, 0.0, 0
    for d in devices:
        ops = [(max(s, lo), min(e, hi), op) for _, s, e, op in
               trace["devices"].get(d, []) if e > lo and s < hi]
        if not ops:
            continue
        seen += 1
        for s, e, op in innermost(ops):
            scope, child = layer_of(op)
            scopes[scope] = scopes.get(scope, 0.0) + (e - s)
            if child:
                key = f"{scope}/{child}"
                children[key] = children.get(key, 0.0) + (e - s)
        merged = trace_mod.union((s, e) for s, e, _ in ops)
        busy += sum(e - s for s, e in merged)
        gaps, prev = [], lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        for k, v in _idle_by_span(gaps, pieces).items():
            idle[k] = idle.get(k, 0.0) + v
    if not seen:
        return None
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy / seen * 1e-9,
            "steps": steps, "scopes_s": _scaled(scopes, 1e-9 / seen),
            "children_s": _scaled(children, 1e-9 / seen),
            "idle_s": _scaled(idle, 1e-9 / seen)}


def _scaled(d: dict, by: float) -> dict:
    return {k: v * by for k, v in sorted(d.items())}


def per_step_ms(red: dict) -> dict:
    """Each time of ``reduce``'s result in ms per step."""
    by = 1e3 / max(red["steps"], 1)
    return {"steps": red["steps"], "window_ms": red["window_s"] * by,
            "busy_ms": red["busy_s"] * by,
            "scopes_ms": _scaled(red["scopes_s"], by),
            "children_ms": _scaled(red["children_s"], by),
            "idle_ms": _scaled(red["idle_s"], by)}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--hlo", help="the compiled step's HLO text, which "
                    "gives each of its operations its op_name")
    args = ap.parse_args(argv)
    hlo = None
    if args.hlo:
        with open(args.hlo) as f:
            hlo = f.read()
    raw = load(trace_mod.xplane_path(args.trace_dir), hlo)
    red = reduce(raw, sorted(raw["devices"]))
    if red is None:
        print("layers: no device operations in the trace", file=sys.stderr)
        return 1
    print(json.dumps(per_step_ms(red)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
