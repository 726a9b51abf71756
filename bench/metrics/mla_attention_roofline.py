"""The latent attention's Pallas kernels' share of their roofline: per
step, the least time the chip needs for their FLOPs and bytes at the
published q.k and v widths (``bench/roofline_mla_moe.py``: the larger
of FLOPs over the bf16 peak and bytes over HBM bandwidth) over their
device self time (``bench/mla_scope.py``)."""
from bench import roofline


def read(rec):
    mla = (rec or {}).get("mla")
    if not mla or not mla["steps"] or not mla["kernel_s"]:
        return None
    peak = roofline.peaks(rec["device_kind"])
    work = rec["mla_kernel_work"]
    least = max(work["flops"] / peak["bf16_flops_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    return roofline.share(least * mla["steps"], mla["kernel_s"])
