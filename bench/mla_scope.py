"""Split the device time of a latent-attention train step's
``attention`` scope, its children and its Pallas kernels.

The program runs latent attention under ``jax.named_scope("attention")``
with children ``latent`` (the projections to q, k and v, RoPE) and
``core`` (the scores and values: on the chip JAX's splash kernels;
``src/repro/models/layers.py`` ``mla_attention``).  A splash kernel's
instruction in the compiled step's HLO text runs over several lines:
its backend config holds an ``xprof_metadata`` string with line breaks,
so its ``op_name`` lies on a later line than its name, and
``bench/layers.py`` (which reads one line per instruction) gives the
kernel no scope.  :func:`join_wrapped` puts each instruction back on
one line before ``layers.load`` reads it; the kernels then count under
their scopes there and here.

:func:`reduce` sums, over the benchmark's window and averaged over the
devices, the self time of the operations under ``attention`` (forward,
recomputed forward and backward alike), of each child, and of the
kernels (operations whose name stack holds ``splash_mha``).
"""
from __future__ import annotations

import re

from bench import layers

SCOPE = "attention"
CHILDREN = ("latent", "core")
KERNEL = "splash_mha"
# the rest of a wrapped instruction: the string's next line, or the
# close of its backend config and the attributes after it
_CONTINUES = re.compile(r'^("|\}.)')


def join_wrapped(hlo_text: str) -> str:
    """The HLO text with every instruction on one line."""
    out = []
    for line in hlo_text.splitlines():
        if out and _CONTINUES.match(line):
            out[-1] += line
        else:
            out.append(line)
    return "\n".join(out)


def child_of(op_name: str) -> str | None:
    """``None`` outside the ``attention`` scope, else the child below it
    (or ``""`` for an operation of the scope itself)."""
    parts = op_name.split("/")
    if SCOPE not in parts:
        return None
    rest = parts[parts.index(SCOPE) + 1:]
    return next((p for p in rest if p in CHILDREN), "")


def reduce(trace: dict, devices) -> dict | None:
    """``{"steps", "attention_s", "children_s", "kernel_s"}`` over the
    window, in seconds averaged over ``devices`` (ids); None where the
    trace holds no operation of those devices."""
    lo, hi = layers.window(trace)
    steps = sum(1 for n, s, e, _ in trace["spans"]
                if n == layers.STEP_SPAN and lo <= (s + e) / 2 < hi)
    total, kernel, seen = 0.0, 0.0, 0
    children = {c: 0.0 for c in CHILDREN}
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
            if KERNEL in op:
                kernel += e - s
    if not seen:
        return None
    return {"steps": steps, "attention_s": total * 1e-9 / seen,
            "children_s": {k: v * 1e-9 / seen for k, v in children.items()},
            "kernel_s": kernel * 1e-9 / seen}
