"""Config system: architecture descriptions, shape specs, registry.

Every assigned architecture is a declarative ``ArchConfig``; the model
zoo (``repro.models.zoo``) interprets it.  Configs are plain frozen
dataclasses — picklable, hashable, diffable — and each architecture file
in ``repro/configs/`` registers one full-size config plus a reduced
``smoke`` variant used by CPU tests.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from enum import Enum
from typing import Callable, Dict, List, Optional, Tuple


class AttentionKind(str, Enum):
    FULL = "full"                # dense causal attention
    SLIDING = "sliding"          # sliding-window (SWA)
    NONE = "none"                # attention-free (SSM layer)
    CROSS = "cross"              # encoder-decoder cross attention
    LATENT = "latent"            # multi-head latent attention (MLA)


class FFNKind(str, Enum):
    DENSE = "dense"
    MOE = "moe"
    NONE = "none"


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # Auxiliary load-balance loss weight (Switch-style).
    aux_loss_weight: float = 0.01
    # Width of one routed expert; 0 -> the config's d_ff.
    expert_ff: int = 0
    # Shared experts, every token through them, held as one SwiGLU of
    # width shared_experts * expert_ff (DeepSeek-MoE).
    shared_experts: int = 0
    # Whether the top-k weights are renormalised to sum to 1, and the
    # factor the routed sum is scaled by.
    renormalize: bool = True
    routed_scale: float = 1.0
    # The routed experts this chip holds (expert parallelism): experts
    # first_held .. first_held + held_experts - 1 of num_experts.  The
    # router still scores all num_experts; held_experts > 0 selects the
    # dropless share path (models.moe.moe_share), which computes only the
    # held experts' part.  0: every expert, capacity dispatch (Mixtral).
    held_experts: int = 0
    first_held: int = 0

    def __post_init__(self):
        if self.held_experts:
            if self.capacity_factor > 0:
                raise ValueError("the expert-share path is dropless: "
                                 "capacity_factor must be <= 0")
            if not 0 <= self.first_held <= self.num_experts - self.held_experts:
                raise ValueError(
                    f"held experts {self.first_held}..+{self.held_experts} "
                    f"lie outside the {self.num_experts} routed experts")


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): keys and values are
    up-projected per head from one shared latent of ``kv_lora_rank``,
    which is what the cache holds, beside one rope key shared by every
    head.  A query head is ``qk_nope_head_dim + qk_rope_head_dim`` wide."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2 configures it."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707

    def __post_init__(self):
        # YaRN scales cos and sin by mscale(mscale) / mscale(mscale_all_dim)
        # and the softmax by mscale(mscale_all_dim)^2; the program
        # implements the second alone, which is exact where they are equal.
        if self.mscale != self.mscale_all_dim:
            raise ValueError("YaRN with mscale != mscale_all_dim is not "
                             "implemented")


@dataclass(frozen=True)
class MambaConfig:
    """Mamba-2 SSD block hyperparameters."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 64


@dataclass(frozen=True)
class LayerSpec:
    """One (possibly repeated) layer 'flavor' in the depth pattern."""

    attention: AttentionKind = AttentionKind.FULL
    ffn: FFNKind = FFNKind.DENSE
    window: int = 0              # >0 for sliding-window layers
    is_mamba: bool = False


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | vlm | hybrid | audio | ssm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads
    # Depth: the ``lead`` layers first (e.g. DeepSeek's leading dense
    # layer), then layer len(lead) + i uses pattern[i % len(pattern)].
    # Default: all-FULL.
    pattern: Tuple[LayerSpec, ...] = (LayerSpec(),)
    lead: Tuple[LayerSpec, ...] = ()
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    rope_scaling: Optional[YarnScaling] = None
    mamba: Optional[MambaConfig] = None
    # Encoder (enc-dec archs only).
    encoder_layers: int = 0
    encoder_seq: int = 0             # fixed source length (stub frontend)
    # Modality stub: inputs arrive as precomputed embeddings of this length.
    frontend_tokens: int = 0         # e.g. image patches prepended to text
    max_seq_len: int = 131072
    rope_theta: float = 500000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # logit soft cap (gemma-style); 0 = off
    logit_softcap: float = 0.0
    # residual scaling (minicpm depth-scaled residuals); 1.0 = off
    residual_scale: float = 1.0
    # parallel attention+FFN block (command-r style)
    parallel_block: bool = False
    # Whether the full-attention path is sub-quadratic enough for long_500k.
    supports_long_context: bool = False
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.num_heads, 1))

    def layer_spec(self, i: int) -> LayerSpec:
        if i < len(self.lead):
            return self.lead[i]
        return self.pattern[(i - len(self.lead)) % len(self.pattern)]

    @property
    def expert_ff(self) -> int:
        """Width of one routed expert."""
        return (self.moe.expert_ff or self.d_ff) if self.moe else self.d_ff

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.resolved_head_dim
        q = d * self.num_heads * hd
        kv = 2 * d * self.num_kv_heads * hd
        o = self.num_heads * hd * d
        attn = q + kv + o
        if self.mla is not None:
            m = self.mla
            qk = m.qk_nope_head_dim + m.qk_rope_head_dim
            latent_attn = (d * self.num_heads * qk
                           + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                           + m.kv_lora_rank
                           + m.kv_lora_rank * self.num_heads
                           * (m.qk_nope_head_dim + m.v_head_dim)
                           + self.num_heads * m.v_head_dim * d)
        dense_ffn = 3 * d * ff  # gated (SwiGLU)
        expert_ffn = 3 * d * self.expert_ff
        total = v * d * (1 if self.tie_embeddings else 2)
        for i in range(self.num_layers):
            spec = self.layer_spec(i)
            if spec.is_mamba and self.mamba is not None:
                m = self.mamba
                d_in = m.expand * d
                nheads = d_in // m.head_dim
                total += d * (2 * d_in + 2 * m.d_state)  # in_proj-ish
                total += d_in * d  # out proj
                total += nheads * m.d_state * m.head_dim // max(nheads, 1)
            elif spec.attention == AttentionKind.LATENT:
                total += latent_attn
            elif spec.attention != AttentionKind.NONE:
                total += attn
            if spec.ffn == FFNKind.MOE and self.moe is not None:
                total += self.moe.num_experts * expert_ffn + d * self.moe.num_experts
                total += self.moe.shared_experts * expert_ffn
            elif spec.ffn == FFNKind.DENSE:
                total += dense_ffn
            total += 2 * d  # norms
        enc_d = d
        for _ in range(self.encoder_layers):
            total += attn + dense_ffn + 2 * enc_d
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only top_k experts)."""
        if self.moe is None:
            return self.param_count()
        dense_ffn = 3 * self.d_model * self.expert_ff
        inactive_experts = self.moe.num_experts - self.moe.top_k
        n_moe_layers = sum(
            1
            for i in range(self.num_layers)
            if self.layer_spec(i).ffn == FFNKind.MOE
        )
        return self.param_count() - n_moe_layers * inactive_experts * dense_ffn


@dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class TrainingConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip_norm: float = 1.0
    schedule: str = "cosine"          # "cosine" | "wsd" | "constant"
    warmup_steps: int = 100
    decay_steps: int = 10000
    stable_steps: int = 0             # WSD only
    microbatch_size: int = 0          # 0 = no accumulation
    remat_policy: str = "none"        # none | full | dots_saveable
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # bf16 moments fit 400B-class models in 16 GB/chip (DESIGN.md §4).
    optimizer_state_dtype: str = "float32"
    grad_compression: str = "none"    # none | int8 | topk
    seed: int = 0


# --- registry ---------------------------------------------------------------

_ARCHS: Dict[str, Tuple[ArchConfig, ArchConfig]] = {}


def register_arch(full: ArchConfig, smoke: ArchConfig) -> ArchConfig:
    _ARCHS[full.name] = (full, smoke)
    return full


def get_arch(name: str, smoke: bool = False) -> ArchConfig:
    import repro.configs  # noqa: F401  (registers everything)

    if name not in _ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(_ARCHS)}")
    full, small = _ARCHS[name]
    return small if smoke else full


def list_archs() -> List[str]:
    import repro.configs  # noqa: F401

    return sorted(_ARCHS)
