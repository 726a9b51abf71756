"""Model / run configuration system.

Every assigned architecture is a :class:`ModelConfig` in its own module
(``repro/configs/<id>.py``); ``repro.configs.get(name)`` resolves them.
Layer heterogeneity (gemma2 local/global, recurrentgemma R-R-A, xlstm
mLSTM/sLSTM) is expressed as a repeating ``block_pattern`` so the model
can scan over pattern groups with stacked params (keeps HLO small enough
to compile 60+ dry-run cells on one CPU core).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int                  # routed experts
    top_k: int
    d_expert: int                   # per-expert FFN hidden dim
    n_shared: int = 0               # always-on shared experts
    capacity_factor: float = 1.25
    router_z_weight: float = 1e-3   # router z-loss
    aux_weight: float = 1e-2        # load-balance aux loss
    # routed experts are zero-padded to a multiple of this (dummy experts
    # are unroutable): 16 lets a 16-way model axis hold them evenly
    # (expert parallelism); 1 holds exactly the published experts
    expert_pad_multiple: int = 16
    # the router (DeepSeek-V3's noaux_tc is "sigmoid" with a bias rate):
    # scores softmax or sigmoid over the routed experts; the top-k gates
    # renormalised over the k, then times routed_scaling
    score_func: str = "softmax"     # softmax | sigmoid
    routed_scaling: float = 1.0
    # > 0: each layer's router holds a load-balancing bias, added to the
    # scores for the top-k choice only, outside the gradient; after each
    # step b_e += bias_rate * sign(mean(c) - c_e) from the step's
    # per-expert routed counts c
    bias_rate: float = 0.0
    # the expert-parallel share: this device holds routed experts
    # [held_first, held_first + n_held) of the n_experts it routes over
    # and computes only their part of the result; None holds them all
    n_held: Optional[int] = None
    held_first: int = 0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3), without a query
    latent: q from one projection; keys and values from a
    ``kv_lora_rank`` latent (normed) and one rope key shared by the
    heads.  q.k is ``qk_nope_head_dim + qk_rope_head_dim`` wide, v
    ``v_head_dim``."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // n_heads

    # layer pattern, cycled to n_layers:
    #   "global" | "local" | "rglru" | "mlstm" | "slstm" | "moe"
    block_pattern: Tuple[str, ...] = ("global",)
    window_size: int = 4096         # local-attention window

    # attention options
    qkv_bias: bool = False
    attn_softcap: Optional[float] = None
    logit_softcap: Optional[float] = None
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0      # chatglm partial rotary = 0.5

    # mlp
    mlp_type: str = "swiglu"        # swiglu | geglu | sqrelu

    moe: Optional[MoEConfig] = None
    # latent attention in every attention layer, in place of GQA
    mla: Optional[MLAConfig] = None
    # leading dense layers ("global" attention + a d_ff MLP) before the
    # block_pattern, which covers the other n_layers - n_dense_layers
    n_dense_layers: int = 0

    # encoder-decoder (seamless): n_layers applies to EACH stack
    encoder_layers: int = 0

    # modality frontend stubs
    frontend: Optional[str] = None  # "vision_stub" | "audio_stub"
    n_frontend_tokens: int = 0
    frontend_dim: int = 0

    norm_eps: float = 1e-6
    post_norm: bool = False         # gemma2: extra post-block norms

    # scaled paths (Granite's muP-style multipliers).  The defaults are
    # the plain decoder's and skip their multiply: embeddings x
    # sqrt(d_model), attention scores x 1/sqrt(head_dim), residual
    # branches and logits unscaled.
    embedding_multiplier: Optional[float] = None   # None: sqrt(d_model)
    attention_multiplier: Optional[float] = None   # None: head_dim ** -0.5
    residual_multiplier: float = 1.0   # x + m * f(x) on every branch
    logits_scaling: float = 1.0        # logits / s
    tie_embeddings: bool = True
    dtype: str = "bfloat16"

    # recurrent blocks
    rglru_conv_width: int = 4
    lru_width: Optional[int] = None

    # TP head padding: production meshes shard attention heads 16-way;
    # archs whose head count doesn't divide (qwen2: 14, granite: 24) get
    # inert padding heads (zero-init wq rows / wo cols — forward-identical
    # at init).  See DESIGN.md "hardware adaptation".
    head_pad_multiple: int = 16

    @property
    def n_heads_padded(self) -> int:
        m = self.head_pad_multiple
        return -(-self.n_heads // m) * m

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def is_subquadratic(self) -> bool:
        """True when no block needs full-sequence quadratic attention
        (long_500k eligibility)."""
        return all(k in ("rglru", "mlstm", "slstm", "local")
                   for k in self.block_pattern)

    def pattern_layout(self) -> Tuple[int, Tuple[str, ...]]:
        """(n_groups, tail_kinds) of the layers after the dense ones:
        n_layers - n_dense_layers = n_groups*len(pattern)+tail."""
        plen = len(self.block_pattern)
        n = self.n_layers - self.n_dense_layers
        return n // plen, self.block_pattern[: n % plen]

    def _attention_params(self) -> int:
        d = self.d_model
        if self.mla is not None:
            a, h = self.mla, self.n_heads
            return (d * h * a.qk_head_dim
                    + d * (a.kv_lora_rank + a.qk_rope_head_dim)
                    + a.kv_lora_rank
                    + a.kv_lora_rank * h * (a.qk_nope_head_dim + a.v_head_dim)
                    + h * a.v_head_dim * d)
        hd = self.resolved_head_dim
        return d * (self.n_heads * hd) * 2 + d * (self.n_kv_heads * hd) * 2

    def _routed_held(self) -> int:
        m = self.moe
        return m.n_experts if m.n_held is None else m.n_held

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks; norms left
        out but the latent's): with a held share, the experts held."""
        d, dff, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.resolved_head_dim
        qo = d * (self.n_heads * hd) * 2
        kv = d * (self.n_kv_heads * hd) * 2
        attn = self._attention_params()
        mlp_mult = 3 if self.mlp_type in ("swiglu", "geglu") else 2
        per_kind = {}
        for kind in set(self.block_pattern):
            if kind in ("global", "local"):
                per_kind[kind] = attn + mlp_mult * d * dff
            elif kind == "rglru":
                w = self.lru_width or d
                per_kind[kind] = 2 * d * w + w * d + 3 * w + mlp_mult * d * dff
            elif kind == "mlstm":
                per_kind[kind] = qo + kv + 2 * d * (2 * d)
            elif kind == "slstm":
                per_kind[kind] = 4 * d * d + 4 * d * d // 4 + 2 * d * (2 * d)
            elif kind == "moe":
                m = self.moe
                e_params = ((self._routed_held() + m.n_shared) * 3 * d
                            * m.d_expert)
                per_kind[kind] = attn + e_params + d * m.n_experts
        n_groups, tail = self.pattern_layout()
        blocks = n_groups * sum(per_kind[k] for k in self.block_pattern)
        blocks += sum(per_kind[k] for k in tail)
        blocks += self.n_dense_layers * (attn + mlp_mult * d * dff)
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.is_encdec:
            blocks *= 2   # encoder + decoder stacks (cross-attn ~ attn)
        if self.frontend:
            emb += self.frontend_dim * d
        return blocks + emb

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed top-k + shared)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        d = self.d_model
        total = self.param_count()
        all_e = self._routed_held() * 3 * d * m.d_expert
        act_e = min(m.top_k, self._routed_held()) * 3 * d * m.d_expert
        n_moe = self.n_layers - self.n_dense_layers
        return total - n_moe * (all_e - act_e)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def runnable_shapes(cfg: ModelConfig) -> Tuple[str, ...]:
    """Which of the 4 assigned shapes this arch runs (spec skip rules):
    no serving shape for latent attention, which has no KV cache here."""
    out = ["train_4k"]
    if cfg.mla is None:
        out += ["prefill_32k", "decode_32k"]
    if cfg.is_subquadratic:
        out.append("long_500k")
    return tuple(out)
