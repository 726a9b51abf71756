"""Share of the traced train window in which no operation ran on the
device (averaged over the chips used)."""


def read(rec):
    if not rec or rec.get("kind") != "train" or not rec.get("trace"):
        return None
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
