"""The comparison that decides ``correct`` for the train cells.

Three numbers, each against a limit from ``bench/limits/<cell>.json``:

- ``loss_gap``: the largest relative gap of a checked step's loss;
- ``grad_norm_gap``: the worst leaf's gap between the program's and
  the reference's norm of the first gradient as AdamW got it (its first
  moment after step 1), over the larger of that leaf's reference norm
  and the median leaf's;
- ``update_norm_gap``: the same for the norm of each leaf's change over
  the checked steps.  Leaves whose reference gradient is under a
  thousandth of the median leaf's (a key bias under softmax: nought to
  rounding) move by round-off alone and are left out of it.
"""
from __future__ import annotations

import math
import statistics

QUIET_LEAF = 1e-3


def leaf_norms(tree) -> list:
    """Float32 L2 norm of every leaf, in flattening order."""
    import jax
    import jax.numpy as jnp
    global _NORMS
    if _NORMS is None:
        _NORMS = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])
    return [float(x) for x in _NORMS(tree)]


_NORMS = None


def _worst(prog, ref, keep, names):
    med = statistics.median([ref[i] for i in keep])
    worst, where = 0.0, ""
    for i in keep:
        gap = abs(prog[i] - ref[i]) / max(ref[i], med, 1e-30)
        if not math.isfinite(gap):
            return math.inf, names[i]
        if gap >= worst:
            worst, where = gap, names[i]
    return worst, where


def gaps(prog: dict, ref: dict, names: list) -> tuple:
    """(``{number: value}``, ``{number: worst leaf}``) of the program's
    readings ``prog`` against the reference's ``ref`` (each with
    ``loss``, ``grad_norms``, ``change_norms``)."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    if not all(math.isfinite(x) for x in prog["loss"]):
        loss = math.inf
    every = list(range(len(names)))
    g, g_leaf = _worst(prog["grad_norms"], ref["grad_norms"], every, names)
    med = statistics.median(ref["grad_norms"])
    moved = [i for i in every if ref["grad_norms"][i] >= QUIET_LEAF * med]
    u, u_leaf = _worst(prog["change_norms"], ref["change_norms"], moved,
                       names)
    return ({"loss_gap": loss, "grad_norm_gap": g, "update_norm_gap": u},
            {"grad_norm_gap": g_leaf, "update_norm_gap": u_leaf})


def judge(values: dict, limits: dict) -> tuple:
    """(correct, checks): every number at or under its limit."""
    checks = {k: {"value": float(values[k]), "limit": float(limits[k])}
              for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
