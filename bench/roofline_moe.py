"""Work counts of the MoE train cells: the model FLOPs of a step
(``train_mfu``; recomputation not counted), and the FLOPs and bytes of
the grouped expert matmuls that ``moe_experts_roofline`` is a share of
(every pass the timed kernels make, the per-layer checkpoint's second
forward included)."""
from __future__ import annotations

BF16_BYTES = 2


def moe_lm_matmul_params(c: dict) -> int:
    """Parameters that take part in a matmul per token of a decoder LM
    whose every layer is GQA attention (no biases) and a top-k MoE of
    SwiGLU experts: each layer's projections, its router and the k
    experts a token is routed to, and the LM head once (a tied
    embedding's lookup is no matmul)."""
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd = d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    router = d * c["num_local_experts"]
    experts = c["num_experts_per_tok"] * 3 * d * c["intermediate_size"]
    return (c["num_hidden_layers"] * (attn + router + experts)
            + c["vocab_size"] * d)


def moe_lm_train_flops(c: dict, seq_len: int, tokens: int) -> float:
    """Model FLOPs of one training step over ``tokens`` tokens of length
    ``seq_len``: 6 per active matmul parameter per token, plus 12 x
    layers x seq x (heads x head_dim) per token for attention scores and
    values (forward and backward)."""
    d = c["hidden_size"]
    attn = 12 * c["num_hidden_layers"] * seq_len * d
    return float(tokens) * (6 * moe_lm_matmul_params(c) + attn)


def experts_work(c: dict, tokens: int, *, recompute: bool) -> dict:
    """FLOPs and HBM bytes of the expert matmuls of one training step
    (``moe/experts``): per layer and pass, the ``tokens * k`` routed rows
    through three (d x f) matmuls, 2*G*k*d*f*3 FLOPs, reading the three
    weight matrices of every expert held and the routed rows in and
    writing them out (bf16).  Forward and backward are three passes, and
    a fourth where the step ``recompute``s the forward (a checkpointed
    layer), which its kernels run and ``moe/experts`` times."""
    d, f = c["hidden_size"], c["intermediate_size"]
    rows = tokens * c["num_experts_per_tok"]
    passes = (3 + bool(recompute)) * c["num_hidden_layers"]
    flops = 2 * rows * d * f * 3
    weights = 3 * c["num_local_experts"] * d * f * BF16_BYTES
    return {"flops": float(passes * flops),
            "bytes": float(passes * (weights + 2 * rows * d * BF16_BYTES))}
