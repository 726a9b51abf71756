"""Device self time per step under the program's latent-attention
``attention`` scope (projections, kernels; forward, recomputed forward
and backward), from the traced window (``bench/mla_scope.py``)."""


def read(rec):
    mla = (rec or {}).get("mla")
    if not mla or not mla["steps"] or not mla["attention_s"]:
        return None
    return 1e3 * mla["attention_s"] / mla["steps"]
