"""Device self time per step under the program's ``moe`` scope
(forward, recomputed forward and backward), from the traced window
(``bench/moe_scope.py``)."""


def read(rec):
    moe = (rec or {}).get("moe")
    if not moe or not moe["steps"]:
        return None
    return 1e3 * moe["moe_s"] / moe["steps"]
