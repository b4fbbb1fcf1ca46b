"""Operations and bytes of a DeepSeek-V3 decode step on one chip's share of
its experts, computed from shapes (``counts.py`` is for dense decoders).

Every function takes a configuration's ``config`` group (the published
``config.json`` keys and the ``expert_share`` cut, as the files under
``configs/`` hold them).  Counts are of the work the algorithm needs, not of
what the program happens to do:

* every weight outside the routed experts is read once a step: the MLA
  projections, the dense lead's MLP, the shared expert, the router, the
  norms and the untied head; the embedding only in the rows the step's
  tokens look up;
* attention in its absorbed form reads the latent rows (``kv_lora_rank``
  + ``qk_rope_head_dim`` per layer) each live slot holds, not the whole
  allocated cache, and writes one new row per slot;
* the routed experts: of n tokens' n * K choices over the router's E
  experts, n * K * E_held / E fall on the E_held experts held here, each
  pair 6 * D * F_moe operations; and the weights of the held experts that
  at least one token chose, E_held * (1 - (1 - K/E)^n) of them in
  expectation.

The expert terms are expectations under independent, near-uniform
routing of the n tokens.  Seeded weights route the tokens of a step
together more than that (a shared correction bias, a common direction in
the residual stream), so fewer distinct experts are chosen than the
expectation says: on the chip the expert kernel, which reads only the
chosen experts' weights, took 0.89 of the time the expectation's bytes
need.  So the expert term is no lower bound on its own; within the whole
decode step it is ~5 % of the bytes, and that step's share stays far
below 100 %.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(c: dict) -> dict:
    share = c.get("expert_share", {})
    return {"D": c["hidden_size"], "H": c["num_attention_heads"], "qr": c["q_lora_rank"],
            "kvr": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "F": c["intermediate_size"], "Fm": c["moe_intermediate_size"],
            "shared": c["n_shared_experts"], "lead": c["first_k_dense_replace"],
            "moe": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "L": c["num_hidden_layers"], "K": c["num_experts_per_tok"],
            "E": share.get("router_experts", c["n_routed_experts"]),
            "held": c["n_routed_experts"], "V": c["vocab_size"]}


def attn_params(c: dict) -> int:
    """One MLA attention's matrices: q down and up, kv down, the rope key,
    k and v up, and the output projection."""
    d = dims(c)
    D, H = d["D"], d["H"]
    return (D * d["qr"] + d["qr"] * H * (d["dn"] + d["dr"]) + D * d["kvr"] + D * d["dr"]
            + d["kvr"] * H * (d["dn"] + d["dv"]) + H * d["dv"] * D)


def expert_params(c: dict) -> int:
    """One routed expert's gated MLP."""
    d = dims(c)
    return 3 * d["D"] * d["Fm"]


def dense_params(c: dict) -> int:
    """Matrices every token passes through, all layers, without the routed
    experts, the embedding and the head: attention, the lead's MLP, the
    shared expert and the router."""
    d = dims(c)
    return (d["L"] * attn_params(c) + d["lead"] * 3 * d["D"] * d["F"]
            + d["moe"] * (d["shared"] * expert_params(c) + d["D"] * d["E"]))


def norm_params(c: dict) -> int:
    """Norm scales and the router's correction biases."""
    d = dims(c)
    return d["L"] * (2 * d["D"] + d["qr"] + d["kvr"]) + d["D"] + d["moe"] * d["E"]


def params(c: dict) -> int:
    """All parameters this chip holds, its experts included."""
    d = dims(c)
    return (dense_params(c) + norm_params(c) + d["moe"] * d["held"] * expert_params(c)
            + 2 * d["V"] * d["D"])


def latent_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """The latent cache of one position over all layers."""
    d = dims(c)
    return d["L"] * (d["kvr"] + d["dr"]) * DTYPE_BYTES[dtype]


def routed(c: dict, n: int, dtype: str) -> tuple[float, float]:
    """(FLOPs, bytes) of the held experts for n tokens, all MoE layers: the
    routed pairs' operations and the chosen experts' weights."""
    d = dims(c)
    pairs = n * d["K"] * d["held"] / d["E"]
    chosen = d["held"] * (1.0 - (1.0 - d["K"] / d["E"]) ** n)
    return (d["moe"] * pairs * 2.0 * expert_params(c),
            d["moe"] * chosen * expert_params(c) * DTYPE_BYTES[dtype])


def decode_step(c: dict, kv_lens: list[int], dtype: str) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over the live slots, where slot i
    attends to ``kv_lens[i]`` positions (its new one included)."""
    d = dims(c)
    n = len(kv_lens)
    b = DTYPE_BYTES[dtype]
    kv = float(sum(kv_lens))
    attn = 2.0 * d["L"] * d["H"] * kv * (2 * d["kvr"] + d["dr"])
    e_flops, e_bytes = routed(c, n, dtype)
    flops = n * 2.0 * (dense_params(c) + d["D"] * d["V"]) + attn + e_flops
    nbytes = ((dense_params(c) + norm_params(c) + d["D"] * d["V"] + n * d["D"]) * b
              + latent_bytes_per_token(c, dtype) * (kv + n) + e_bytes)
    return flops, float(nbytes)


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
