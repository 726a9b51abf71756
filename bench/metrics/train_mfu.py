"""Model FLOPs of the traced window's steps per second, as a share of
the chips' bf16 peak (``bench/roofline.py``; recomputation is not
counted)."""
from bench import roofline


def read(rec):
    if not rec or rec.get("kind") != "train" or not rec.get("trace"):
        return None
    steps_per_s = len(rec["steps"]) / rec["trace"]["window_s"]
    per_s = rec["flops_per_step"] * steps_per_s
    peak = roofline.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return roofline.share(per_s, rec["chips"] * peak)
