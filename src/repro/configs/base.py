"""Model / run configuration system.

Every assigned architecture gets one module in ``repro.configs`` exporting
``CONFIG`` (the exact public-literature configuration) and ``tiny()`` (a
reduced same-family config for CPU smoke tests).  ``repro.configs.get_config``
is the registry entry point used by the launcher (``--arch <id>``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description (family-general superset).

    Only fields relevant to a family are consumed by its block builder; the
    rest stay at defaults.  All shapes follow the assignment table verbatim.
    """

    name: str
    family: str                      # dense | moe | hybrid | vlm | audio | ssm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads

    # --- attention ---
    attention: str = "gqa"           # gqa | mla | mqa
    qkv_bias: bool = False
    rope_style: str = "standard"     # standard | mrope | partial | none
    rope_fraction: float = 1.0       # fraction of head_dim rotated (phi4 partial rope)
    rope_theta: float = 10_000.0
    # YaRN (DeepSeek-V3's ``rope_scaling``); factor 0 = plain RoPE
    yarn_factor: float = 0.0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_all_dim: float = 0.0     # softmax scale times mscale(factor, this)^2
    yarn_original_max_pos: int = 4096
    window: int = 0                  # sliding-window size (0 = full attention)
    logit_soft_cap: float = 0.0

    # --- ffn ---
    act: str = "swiglu"              # swiglu | geglu | gelu

    # --- norm / embedding ---
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    norm_eps: float = 1e-6
    rms_offset: bool = False         # gemma-style (1 + w) RMSNorm weight
    scale_embedding: bool = False    # gemma-style sqrt(d_model) embed scale
    tie_embeddings: bool = False

    # --- MoE ---
    num_experts: int = 0             # the router's width: every expert it scores
    top_k: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0                # per-expert hidden size
    router_aux_coef: float = 0.01
    # router: softmax (top-k of a softmax) | noaux_tc (DeepSeek-V3: sigmoid
    # scores, a per-expert correction bias that picks but does not weigh,
    # top ``topk_group`` of ``n_group`` groups, then top-k); the k weights
    # are normalised, then scaled by ``routed_scaling_factor``
    router: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # this device's share of the experts: ``num_experts_held`` of them from
    # ``expert_offset`` (0 -> all); the router still scores ``num_experts``
    num_experts_held: int = 0
    expert_offset: int = 0
    first_k_dense: int = 0           # leading dense layers (width d_ff) before the MoE ones

    # --- MLA (deepseek) ---
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- hybrid / ssm block pattern ---
    block_pattern: tuple[str, ...] = ()   # cycle of block kinds, e.g. ("rec","rec","attn")
    lru_width: int = 0                    # RG-LRU recurrence width (0 -> d_model)
    lru_gate_blocks: int = 1              # block-diagonal gate matrices (Griffin App. A)
    conv_width: int = 4                   # temporal conv kernel for recurrent blocks
    mlstm_proj_factor: float = 2.0        # xLSTM mLSTM up-projection
    slstm_proj_factor: float = 4.0 / 3.0  # xLSTM sLSTM FFN factor
    chunk_size: int = 256                 # chunkwise-parallel recurrence chunk

    # --- encoder/decoder (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 1500               # whisper: 30 s of audio -> 1500 frames
    cross_attention: bool = False

    # --- multimodal stub frontend ---
    frontend: str = "none"                # none | audio_frames | vision_patches

    # --- numerics ---
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    citation: str = ""

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))
        if self.expert_offset + self.held_experts > self.num_experts:
            raise ValueError(f"{self.name}: experts {self.expert_offset}.."
                             f"{self.expert_offset + self.held_experts - 1} outside the "
                             f"router's {self.num_experts}")

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def held_experts(self) -> int:
        """How many experts this device holds and computes."""
        return self.num_experts_held or self.num_experts

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter counts (used by roofline MODEL_FLOPS = 6 N D) ----
    def param_count(self, active_only: bool = False) -> int:
        """Total (or MoE-active) parameter count, embedding included."""
        from repro.models.params import count_params
        return count_params(self, active_only=active_only)


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str            # train | prefill | decode
    sub_quadratic_only: bool = False


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode", sub_quadratic_only=True),
}


@dataclass(frozen=True)
class RunConfig:
    """Parallelism / training-run knobs consumed by the launcher and dry-run."""

    model: ModelConfig
    shape: ShapeConfig
    # mesh logical sizes (products must equal device count)
    pod: int = 1
    data: int = 16
    model_axis: int = 16
    # distribution features
    zero_stage: int = 1              # 0 off, 1 opt-state, 2 +grads, 3 +params (FSDP)
    remat_policy: str = "block"      # none | block | dots
    optimizer: str = "adamw"         # adamw | adafactor
    microbatches: int = 1            # grad-accumulation microbatches
    grad_compression: str = "none"   # none | int8
    extra: dict[str, Any] = field(default_factory=dict)


def supports_shape(model: ModelConfig, shape: ShapeConfig) -> bool:
    """Shape applicability per the assignment.

    ``long_500k`` needs sub-quadratic attention: only hybrid (windowed attn +
    recurrent state) and ssm families qualify; pure full-attention archs skip
    it (recorded in DESIGN.md §Arch-applicability).
    """
    if shape.sub_quadratic_only:
        return model.family in ("hybrid", "ssm")
    return True
