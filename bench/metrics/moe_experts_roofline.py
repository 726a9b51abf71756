"""The grouped expert matmuls' share of their roofline: per step, the
least time the chip needs for their FLOPs and bytes
(``bench/roofline_moe.py``: the larger of FLOPs over the bf16 peak and
bytes over HBM bandwidth) over their device self time under
``moe/experts`` (``bench/moe_scope.py``)."""
from bench import roofline


def read(rec):
    moe = (rec or {}).get("moe")
    if not moe or not moe["steps"] or not moe["children_s"]["experts"]:
        return None
    peak = roofline.peaks(rec["device_kind"])
    work = rec["experts_work"]
    least = max(work["flops"] / peak["bf16_flops_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    return roofline.share(least * moe["steps"], moe["children_s"]["experts"])
