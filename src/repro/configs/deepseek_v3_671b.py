"""DeepSeek-V3 671B — MLA attention, 3 dense layers, then 58 layers of 256
routed experts (top-8, sigmoid noaux_tc router) and 1 shared expert; YaRN.

[arXiv:2412.19437]; the published ``config.json``
(https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json).
Departures from it, each a choice of this repository:

* MTP: the one multi-token-prediction layer (``num_nextn_predict_layers``
  1) is not built; it serves only training and speculative decoding.
* Weights: the release is FP8 with 128x128 block scales; the model here
  computes in ``dtype`` (bf16) and keeps no scales.
* RoPE pairs: dimension i rotates with i + 32 of the 64 rope dims; the
  checkpoint's interleaved pairs are a fixed permutation of the rope
  projections' columns, the same model under other weights.
* Training: the router's sequence-wise balance loss (alpha 1e-4) is not
  computed; the correction bias is a parameter, not updated by the
  aux-loss-free balancing rule.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    num_layers=61,
    first_k_dense=3,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,        # MLA: KV latent is shared; head count used for expanded form
    head_dim=128,
    d_ff=18432,              # the dense lead's MLP
    vocab_size=129280,
    attention="mla",
    act="swiglu",
    norm_eps=1e-6,
    num_experts=256,
    top_k=8,
    num_shared_experts=1,
    moe_d_ff=2048,
    router="noaux_tc",
    n_group=8,
    topk_group=4,
    routed_scaling_factor=2.5,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    yarn_factor=40.0,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale_all_dim=1.0,
    yarn_original_max_pos=4096,
    citation="arXiv:2412.19437",
)


def tiny() -> ModelConfig:
    """One dense lead layer and two MoE layers; 4 groups of 2 experts, top 2
    groups; YaRN with its ramp inside the 4 rope frequencies."""
    return CONFIG.replace(
        name="deepseek-v3-tiny", num_layers=3, first_k_dense=1, d_model=64, num_heads=4,
        num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=512,
        num_experts=8, top_k=2, num_shared_experts=1, moe_d_ff=32, n_group=4, topk_group=2,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, yarn_factor=4.0, yarn_original_max_pos=64,
    )
