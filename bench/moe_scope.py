"""Split the device time of an MoE train step's ``moe`` scope.

The program runs each MoE block under ``jax.named_scope("moe")``, with
children ``route``, ``dispatch``, ``experts`` and ``combine``
(``src/repro/models/moe.py``).  That scope lies inside ``fwd_bwd``, so
``bench/layers.py`` counts it there; this reduction works on the same
loaded trace (``layers.load``, which gives each operation its op_name
from the compiled step's HLO) and sums the self time of the operations
whose op_name has ``moe`` in its name stack, forward, recomputed
forward and backward alike, and of each child below it, over the
benchmark's window, averaged over the devices.
"""
from __future__ import annotations

from bench import layers

SCOPE = "moe"
CHILDREN = ("route", "dispatch", "experts", "combine")


def child_of(op_name: str) -> str | None:
    """``None`` outside the ``moe`` scope, else the child below it (or
    ``""`` for an operation of the scope itself)."""
    parts = op_name.split("/")
    if SCOPE not in parts:
        return None
    rest = parts[parts.index(SCOPE) + 1:]
    return next((p for p in rest if p in CHILDREN), "")


def reduce(trace: dict, devices) -> dict | None:
    """``{"steps", "moe_s", "children_s"}`` over the window, in seconds
    averaged over ``devices`` (ids); None where the trace holds no
    operation of those devices."""
    lo, hi = layers.window(trace)
    steps = sum(1 for n, s, e, _ in trace["spans"]
                if n == layers.STEP_SPAN and lo <= (s + e) / 2 < hi)
    total, children, seen = 0.0, {c: 0.0 for c in CHILDREN}, 0
    for d in devices:
        ops = [(max(s, lo), min(e, hi), op) for _, s, e, op in
               trace["devices"].get(d, []) if e > lo and s < hi]
        if not ops:
            continue
        seen += 1
        for s, e, op in layers.innermost(ops):
            child = child_of(op)
            if child is None:
                continue
            total += e - s
            if child:
                children[child] += e - s
    if not seen:
        return None
    return {"steps": steps, "moe_s": total * 1e-9 / seen,
            "children_s": {k: v * 1e-9 / seen for k, v in children.items()}}
