"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists: per device, its operations as ``(name, start_ns, end_ns)``;
and the host's events, the benchmark's own spans (``bench.*``) among
them.  ``reduce`` then works on those lists alone, so it can be tested
on a synthetic event list:

- busy: the union of a device's operation intervals inside the window
  (the benchmark's ``bench.window`` span), averaged over the devices;
- the top device operations by summed time;
- collective time on the first device, and the part of it during which
  no other operation runs there;
- the longest idle gaps of the first device, each named by what the
  host was doing: the shortest host event that covers at least half of
  the gap.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# XLA's collective operations, as they are named in the ops line
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|psum|ragged-all-to-all|send|recv)", re.IGNORECASE)


def xplane_path(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> dict:
    """``{"devices": {id: [(name, start, end)]}, "host": [...]}`` from
    an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        lines = list(plane.lines)
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            by_name = {ln.name: ln for ln in lines}
            ops = by_name.get("XLA Ops")
            if ops is None:
                continue
            devices[int(m.group(2))] = [
                (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in ops.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                host.extend((ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in ln.events)
    return {"devices": devices, "host": host}


def op_name(name: str) -> str:
    """``fusion.464`` from an event named by its whole HLO instruction
    (``%fusion.464 = (bf16[...]) fusion(...)``)."""
    return name.lstrip("%").split(" = ", 1)[0].split(" ", 1)[0]


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def _subtract(a, b) -> float:
    """Length of merged ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def window(trace: dict) -> tuple:
    spans = [(s, e) for n, s, e in trace["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(trace: dict, devices, *, top: int = 10) -> dict | None:
    """The numbers of one traced window over ``devices`` (ids); None
    where the trace holds no operation of those devices."""
    lo, hi = window(trace)
    busy, per_dev = [], {}
    for d in devices:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in
               trace["devices"].get(d, []) if e > lo and s < hi]
        per_dev[d] = ops
        busy.append(_length(union((s, e) for _, s, e in ops)))
    if not any(per_dev.values()):
        return None
    totals = {}
    for ops in per_dev.values():
        for n, s, e in ops:
            totals[n] = totals.get(n, 0.0) + (e - s)
    n_dev = len(per_dev)
    top_ops = sorted(((n, t / n_dev * 1e-9) for n, t in totals.items()),
                     key=lambda x: -x[1])[:top]
    first = per_dev[devices[0]]
    coll = union((s, e) for n, s, e in first if COLLECTIVE.match(n))
    other = union((s, e) for n, s, e in first if not COLLECTIVE.match(n))
    busy0 = union((s, e) for _, s, e in first)
    gaps, prev = [], lo
    for s, e in busy0 + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "busy_s_per_device": [b * 1e-9 for b in busy],
        "top_ops": [[n, t] for n, t in top_ops],
        "collective_s": _length(coll) * 1e-9,
        "collective_exposed_s": _subtract(coll, other) * 1e-9,
        "idle_gaps": [[host_activity(trace["host"], s, e), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def host_activity(host, lo, hi) -> str:
    """The shortest host event covering at least half of ``[lo, hi)``."""
    need = 0.5 * (hi - lo)
    best = None
    for n, s, e in host:
        if n == WINDOW_SPAN:
            continue
        if min(e, hi) - max(s, lo) >= need and (
                best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "(no host event)"
