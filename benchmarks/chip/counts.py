"""Operations and bytes of the benchmark's work, computed from shapes.

Every function takes a configuration's ``config`` group (the published
``config.json`` keys, as the files under ``configs/`` hold them).  Counts
are of the work the algorithm needs, not of what the program happens to
do: decode attention reads only the rows a slot holds, causal attention
counts only the scores at or below the diagonal, and recomputation in the
backward pass is not counted.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(c: dict) -> dict:
    """Model sizes under short names."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    return {"D": D, "H": H, "Hkv": c["num_key_value_heads"],
            "Dh": c.get("head_dim") or D // H, "F": c["intermediate_size"],
            "L": c["num_hidden_layers"], "V": c["vocab_size"]}


def layer_matmul_params(c: dict) -> int:
    """Weights of one decoder layer that enter a matrix product:
    q, k, v, o and the gated MLP's gate, up and down."""
    d = dims(c)
    attn = d["D"] * (d["H"] + 2 * d["Hkv"]) * d["Dh"] + d["H"] * d["Dh"] * d["D"]
    return attn + 3 * d["D"] * d["F"]


def params(c: dict) -> int:
    """All parameters: layers with their two norms, the embedding, the final
    norm, and an output head unless it is tied to the embedding."""
    d = dims(c)
    head = 0 if c.get("tie_word_embeddings") else d["V"] * d["D"]
    return (d["L"] * (layer_matmul_params(c) + 2 * d["D"])
            + d["V"] * d["D"] + d["D"] + head)


def weight_bytes(c: dict, dtype: str) -> int:
    return params(c) * DTYPE_BYTES[dtype]


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """Keys and values of one position over all layers."""
    d = dims(c)
    return 2 * d["L"] * d["Hkv"] * d["Dh"] * DTYPE_BYTES[dtype]


def _attn_flops(c: dict, queries_keys: float) -> float:
    """QK^T and PV over ``queries_keys`` (query, key) pairs, all layers."""
    d = dims(c)
    return 4.0 * d["L"] * d["H"] * d["Dh"] * queries_keys


def decode_step(c: dict, kv_lens: list[int], dtype: str) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over the active slots, where slot i
    attends to ``kv_lens[i]`` positions (its new one included).  Bytes: every
    weight once (the tied head reads the whole embedding), the keys and
    values those positions hold, and the new rows written."""
    d = dims(c)
    n = len(kv_lens)
    flops = (n * 2.0 * (d["L"] * layer_matmul_params(c) + d["D"] * d["V"])
             + _attn_flops(c, float(sum(kv_lens))))
    kv = kv_bytes_per_token(c, dtype)
    return flops, float(weight_bytes(c, dtype) + kv * (sum(kv_lens) + n))


def prefill(c: dict, prompt_len: int, dtype: str) -> tuple[float, float]:
    """(FLOPs, bytes) of one batch-1 prefill of ``prompt_len`` tokens that
    returns the last position's logits.  Causal attention counts the
    S(S+1)/2 pairs at or below the diagonal.  Bytes: every weight once and
    the prompt's keys and values written."""
    d = dims(c)
    S = prompt_len
    flops = (S * 2.0 * d["L"] * layer_matmul_params(c) + 2.0 * d["D"] * d["V"]
             + _attn_flops(c, S * (S + 1) / 2.0))
    return flops, float(weight_bytes(c, dtype) + kv_bytes_per_token(c, dtype) * S)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Model FLOPs of forward and backward per trained token: 6 per matmul
    weight (layers and the head) plus causal attention, whose scores at or
    below the diagonal average (S+1)/2 keys per query.  Recomputation is
    not counted."""
    d = dims(c)
    matmul = d["L"] * layer_matmul_params(c) + d["D"] * d["V"]
    return 6.0 * matmul + 3.0 * _attn_flops(c, (seq_len + 1) / 2.0)


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
