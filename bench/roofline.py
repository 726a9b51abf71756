"""Device peaks and the work counts that shares of them are taken from.

The peaks table (``bench/peaks.json``) is keyed by JAX's
``device_kind``; a device that is not in it is an error, not a default.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
MAX_SHARE = 105.0   # a share above this counts work too high or time too low


class UnknownDevice(KeyError):
    pass


class ShareTooHigh(ValueError):
    pass


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(table)}")
    return table[device_kind]


def dense_lm_matmul_params(c: dict) -> int:
    """Parameters that take part in a matmul per token of a dense
    decoder LM with GQA and a SwiGLU MLP: the projections of every
    layer, and the LM head once (a tied embedding's lookup is no
    matmul)."""
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd = d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * (attn + mlp) + c["vocab_size"] * d


def dense_lm_train_flops(c: dict, seq_len: int, tokens: int) -> float:
    """Model FLOPs of one training step over ``tokens`` tokens of length
    ``seq_len``: 6 per matmul parameter per token, plus 12 x layers x
    seq x (heads x head_dim) per token for attention scores and values
    (forward and backward; recomputation is not counted)."""
    d = c["hidden_size"]
    attn = 12 * c["num_hidden_layers"] * seq_len * d
    return float(tokens) * (6 * dense_lm_matmul_params(c) + attn)


def share(achieved: float, peak: float) -> float:
    """``achieved`` as a percentage of ``peak``; refuses one above
    ``MAX_SHARE`` instead of hiding it."""
    pct = 100.0 * achieved / peak
    if pct > MAX_SHARE:
        raise ShareTooHigh(f"{pct:.1f}% of peak: the work is counted too "
                           "high or the time leaves part of it out")
    return pct
