"""Work counts of the latent-attention MoE train cells (DeepSeek-V3's
block, ``bench/configs/moonlight-16b-a3b.json``): the model FLOPs of a
step (``train_mfu``; recomputation not counted), and the FLOPs and
bytes of the two parts that per-layer rooflines are shares of, in every
pass the timed kernels make (the per-layer checkpoint's second forward
included):

- the latent attention's Pallas kernels (``mla_attention_roofline``):
  causal scores and values at the published q.k and v widths;
- the held experts' grouped matmuls (``moe_experts_roofline``): the rows
  routed to the held experts, as the program counts them.

Causal attention counts the lower triangle: S^2/2 scores a head."""
from __future__ import annotations

BF16_BYTES = 2


def _widths(c: dict) -> tuple:
    return (c["hidden_size"], c["num_attention_heads"],
            c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["v_head_dim"])


def attention_params(c: dict) -> int:
    """Matmul parameters of one latent attention: q, the latent and
    shared rope key, each head's key and value from the latent, out."""
    d, h, qk, dv = _widths(c)
    lat, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return (d * h * qk + d * (lat + rope)
            + lat * h * (c["qk_nope_head_dim"] + dv) + h * dv * d)


def _layers(c: dict) -> tuple:
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def dense_matmul_params(c: dict) -> int:
    """Parameters every token passes through a matmul of: every layer's
    attention, the dense layers' MLPs, each MoE layer's router (over
    every routed expert) and shared experts, and the untied head once
    (the embedding's lookup is no matmul).  The routed experts are
    counted by rows (:func:`train_flops`)."""
    d = c["hidden_size"]
    n_dense, n_moe = _layers(c)
    shared = 3 * d * c["n_shared_experts"] * c["moe_intermediate_size"]
    return (c["num_hidden_layers"] * attention_params(c)
            + n_dense * 3 * d * c["intermediate_size"]
            + n_moe * (d * c["experts_routed_over"] + shared)
            + c["vocab_size"] * d)


def attention_flops(c: dict, seq_len: int) -> float:
    """One layer's causal scores and values of one sequence, forward:
    2 x (S^2 / 2) x heads x (q.k width + v width)."""
    _, h, qk, dv = _widths(c)
    return float(seq_len) ** 2 * h * (qk + dv)


def train_flops(c: dict, seq_len: int, tokens: int,
                held_rows: float) -> float:
    """Model FLOPs of one training step (forward and backward, 3 x the
    forward) over ``tokens`` tokens in sequences of ``seq_len``: 6 per
    matmul parameter per token, 6 x 3 x d x f per row routed to a held
    expert (``held_rows`` a MoE layer), and every layer's causal
    attention."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    _, n_moe = _layers(c)
    seqs = tokens // seq_len
    return (6.0 * tokens * dense_matmul_params(c)
            + 6.0 * n_moe * held_rows * 3 * d * f
            + 3.0 * seqs * c["num_hidden_layers"]
            * attention_flops(c, seq_len))


def attention_kernel_work(c: dict, seq_len: int, seqs: int) -> dict:
    """FLOPs and HBM bytes of the latent attention's Pallas kernels in a
    training step whose layers are checkpointed: per layer and sequence
    the forward kernel twice (forward, recompute), the dq kernel (QK^T,
    dO V^T, dS K) and the dkv kernel (QK^T, dO V^T, P^T dO, dS^T Q),
    each matmul over the S^2/2 causal scores of every head; bytes: each
    kernel's bf16 operands read once and its results written once."""
    _, h, qk, dv = _widths(c)
    tri = float(seq_len) ** 2 * h          # 2 x S^2/2 x heads
    rows = float(seq_len) * h * BF16_BYTES  # one value a head and token
    flops = tri * (2 * (qk + dv) + (2 * qk + dv) + (2 * qk + 2 * dv))
    # forward: q, k, v in, o out; dq: q, k, v, o, dO in, dq out; dkv:
    # q, k, v, dO in, dk, dv out
    byts = rows * (2 * (2 * qk + 2 * dv) + (3 * qk + 3 * dv)
                   + (3 * qk + 3 * dv))
    n = seqs * c["num_hidden_layers"]
    return {"flops": n * flops, "bytes": n * byts}


def experts_work(c: dict, held_rows: float, *, recompute: bool) -> dict:
    """FLOPs and HBM bytes of the held experts' grouped matmuls of one
    training step (``moe/experts``): per MoE layer and pass, the
    ``held_rows`` rows routed to the held experts through three (d x f)
    matmuls, reading the three weight matrices of every held expert and
    the rows in and out (bf16).  Forward and backward are three passes,
    and a fourth where the step ``recompute``s the forward."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    _, n_moe = _layers(c)
    passes = (3 + bool(recompute)) * n_moe
    weights = 3 * c["n_routed_experts"] * d * f * BF16_BYTES
    return {"flops": float(passes * 2 * held_rows * d * f * 3),
            "bytes": float(passes * (weights
                                     + 2 * held_rows * d * BF16_BYTES))}
