"""Parameter-tree builder for the architecture zoo.

One builder (`build_params`) drives four consumers via a creator callback:
  * abstract shapes   (`abstract_params`)  — ShapeDtypeStruct, no allocation
  * concrete init     (`init_params`)      — PRNG-initialised arrays
  * sharding specs    (`param_pspecs`)     — logical axes -> PartitionSpec
  * parameter counts  (`count_params`)

Block parameters are *stacked* over cycle repetitions (leading 'layer' dim)
so the model can `lax.scan` over depth; a non-divisible remainder lives under
``blocks['tail']`` unstacked, and leading dense layers (DeepSeek-V3's
``first_k_dense_replace``) under ``blocks['lead']``, unstacked, ahead of the
scan; a model without them has no ``lead`` entry.
"""
from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Creator = Callable[..., object]  # creator(path, shape, logical, fan_in) -> leaf


# --------------------------------------------------------------------------
# Block cycle resolution
# --------------------------------------------------------------------------

def lead_blocks(cfg: ModelConfig) -> tuple[str, ...]:
    """Kinds of the leading dense layers, run before the scanned cycle."""
    return ("mla_ffn" if cfg.attention == "mla" else "attn_ffn",) * cfg.first_k_dense


def block_cycle(cfg: ModelConfig) -> tuple[tuple[str, ...], int, tuple[str, ...]]:
    """Return (cycle_kinds, n_cycles, tail_kinds) for the decoder stack after
    the lead (:func:`lead_blocks`)."""
    if cfg.family in ("dense", "vlm"):
        cycle = ("attn_ffn",)
    elif cfg.family == "moe":
        cycle = ("moe_attn_ffn" if cfg.attention != "mla" else "mla_moe",)
    elif cfg.family == "hybrid":
        cycle = tuple("griffin_rec" if k == "rec" else "griffin_attn" for k in cfg.block_pattern)
    elif cfg.family == "ssm":
        cycle = cfg.block_pattern
    elif cfg.family == "audio":
        cycle = ("xattn",)
    else:
        raise ValueError(cfg.family)
    layers = cfg.num_layers - cfg.first_k_dense
    n = layers // len(cycle)
    tail_len = layers - n * len(cycle)
    return cycle, n, cycle[:tail_len]


# --------------------------------------------------------------------------
# Per-kind parameter definitions
# --------------------------------------------------------------------------

def _norm(cfg, c: Creator, path):
    p = {"w": c(path + ("w",), (cfg.d_model,), ("embed",), 0)}
    if cfg.norm == "layernorm":
        p["b"] = c(path + ("b",), (cfg.d_model,), ("embed",), 0)
    return p


def _vec_norm(cfg, c, path, dim):
    return {"w": c(path + ("w",), (dim,), (None,), 0)}


def _gqa_attn(cfg, c: Creator, path, *, kv_heads=None, bias=None):
    D, H = cfg.d_model, cfg.num_heads
    Hkv = kv_heads if kv_heads is not None else cfg.num_kv_heads
    Dh = cfg.head_dim
    bias = cfg.qkv_bias if bias is None else bias
    p = {
        "q": {"w": c(path + ("q", "w"), (D, H, Dh), ("embed", "heads", "head_dim"), D)},
        "k": {"w": c(path + ("k", "w"), (D, Hkv, Dh), ("embed", "kv_heads", "head_dim"), D)},
        "v": {"w": c(path + ("v", "w"), (D, Hkv, Dh), ("embed", "kv_heads", "head_dim"), D)},
        "o": {"w": c(path + ("o", "w"), (H, Dh, D), ("heads", "head_dim", "embed"), H * Dh)},
    }
    if bias:
        p["q"]["b"] = c(path + ("q", "b"), (H, Dh), ("heads", "head_dim"), 0)
        p["k"]["b"] = c(path + ("k", "b"), (Hkv, Dh), ("kv_heads", "head_dim"), 0)
        p["v"]["b"] = c(path + ("v", "b"), (Hkv, Dh), ("kv_heads", "head_dim"), 0)
    return p


def _mla_attn(cfg, c: Creator, path):
    D, H = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "dq": {"w": c(path + ("dq", "w"), (D, qr), ("embed", None), D)},
        "q_norm": _vec_norm(cfg, c, path + ("q_norm",), qr),
        "uq": {"w": c(path + ("uq", "w"), (qr, H, dn + dr), (None, "heads", "head_dim"), qr)},
        "dkv": {"w": c(path + ("dkv", "w"), (D, kvr), ("embed", None), D)},
        "kv_norm": _vec_norm(cfg, c, path + ("kv_norm",), kvr),
        "uk": {"w": c(path + ("uk", "w"), (kvr, H, dn), (None, "heads", "head_dim"), kvr)},
        "uv": {"w": c(path + ("uv", "w"), (kvr, H, dv), (None, "heads", "head_dim"), kvr)},
        "kr": {"w": c(path + ("kr", "w"), (D, dr), ("embed", None), D)},
        "o": {"w": c(path + ("o", "w"), (H, dv, D), ("heads", "head_dim", "embed"), H * dv)},
    }


def _mlp(cfg, c: Creator, path, d_ff=None, *, bias=False):
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    p = {}
    if cfg.act in ("swiglu", "geglu"):
        p["gate"] = {"w": c(path + ("gate", "w"), (D, F), ("embed", "ffn"), D)}
    p["up"] = {"w": c(path + ("up", "w"), (D, F), ("embed", "ffn"), D)}
    p["down"] = {"w": c(path + ("down", "w"), (F, D), ("ffn", "embed"), F)}
    if bias:
        p["up"]["b"] = c(path + ("up", "b"), (F,), ("ffn",), 0)
        p["down"]["b"] = c(path + ("down", "b"), (D,), ("embed",), 0)
        if "gate" in p:
            p["gate"]["b"] = c(path + ("gate", "b"), (F,), ("ffn",), 0)
    return p


def _moe(cfg, c: Creator, path):
    """The router scores all ``num_experts``; expert weights exist only for
    the ``held_experts`` this device computes."""
    D, E, F = cfg.d_model, cfg.held_experts, cfg.moe_d_ff
    router = {"w": c(path + ("router", "w"), (D, cfg.num_experts), ("embed", None), D)}
    if cfg.router == "noaux_tc":
        router["bias"] = c(path + ("router", "bias"), (cfg.num_experts,), (None,), 0)
    p = {
        "router": router,
        "experts": {
            "gate": c(path + ("experts", "gate"), (E, D, F), ("expert", "embed", "expert_ffn"), D),
            "up": c(path + ("experts", "up"), (E, D, F), ("expert", "embed", "expert_ffn"), D),
            "down": c(path + ("experts", "down"), (E, F, D), ("expert", "expert_ffn", "embed"), F),
        },
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = _mlp(cfg, c, path + ("shared",), cfg.moe_d_ff * cfg.num_shared_experts)
    return p


def _rglru_gates(cfg, c: Creator, path, W: int):
    nb = max(cfg.lru_gate_blocks, 1)
    if nb > 1:
        # Griffin Appendix: block-diagonal recurrence/input gates — keeps the
        # gate matmuls local under width sharding (no TP all-reduce)
        Wb = W // nb
        shp, ax = (nb, Wb, Wb), ("lru_width", None, None)
    else:
        shp, ax = (W, W), ("lru_width", None)
    return {
        "wa": c(path + ("rglru", "wa"), shp, ax, shp[-1]),
        "ba": c(path + ("rglru", "ba"), (W,), (None,), 0),
        "wx": c(path + ("rglru", "wx"), shp, ax, shp[-1]),
        "bx": c(path + ("rglru", "bx"), (W,), (None,), 0),
        "lam": c(path + ("rglru", "lam"), (W,), (None,), 0),
    }


def _griffin_rec(cfg, c: Creator, path):
    D, W, K = cfg.d_model, cfg.lru_width or cfg.d_model, cfg.conv_width
    return {
        "ln": _norm(cfg, c, path + ("ln",)),
        "in_gate": {"w": c(path + ("in_gate", "w"), (D, W), ("embed", "lru_width"), D)},
        "in_rec": {"w": c(path + ("in_rec", "w"), (D, W), ("embed", "lru_width"), D)},
        "conv": {"w": c(path + ("conv", "w"), (K, W), (None, "lru_width"), 0),
                 "b": c(path + ("conv", "b"), (W,), ("lru_width",), 0)},
        "rglru": _rglru_gates(cfg, c, path, W),
        "out": {"w": c(path + ("out", "w"), (W, D), ("lru_width", "embed"), W)},
        "ln2": _norm(cfg, c, path + ("ln2",)),
        "mlp": _mlp(cfg, c, path + ("mlp",)),
    }


def _griffin_attn(cfg, c: Creator, path):
    return {
        "ln": _norm(cfg, c, path + ("ln",)),
        "attn": _gqa_attn(cfg, c, path + ("attn",)),
        "ln2": _norm(cfg, c, path + ("ln2",)),
        "mlp": _mlp(cfg, c, path + ("mlp",)),
    }


def _mlstm_block(cfg, c: Creator, path):
    D = cfg.d_model
    Di = int(cfg.mlstm_proj_factor * D)
    H, Dh = cfg.num_heads, cfg.head_dim
    DQ = H * Dh
    return {
        "ln": _norm(cfg, c, path + ("ln",)),
        "up": {"w": c(path + ("up", "w"), (D, Di), ("embed", "ffn"), D)},
        "conv": {"w": c(path + ("conv", "w"), (cfg.conv_width, Di), (None, "ffn"), 0),
                 "b": c(path + ("conv", "b"), (Di,), ("ffn",), 0)},
        "q": {"w": c(path + ("q", "w"), (Di, DQ), ("ffn", None), Di)},
        "k": {"w": c(path + ("k", "w"), (Di, DQ), ("ffn", None), Di)},
        "v": {"w": c(path + ("v", "w"), (Di, DQ), ("ffn", None), Di)},
        "gates": {"w": c(path + ("gates", "w"), (Di, 2 * H), ("ffn", None), Di),
                  "b": c(path + ("gates", "b"), (2 * H,), (None,), 0)},
        "out_norm": _vec_norm(cfg, c, path + ("out_norm",), DQ),
        "z": {"w": c(path + ("z", "w"), (D, DQ), ("embed", None), D)},
        "o": {"w": c(path + ("o", "w"), (DQ, D), (None, "embed"), DQ)},
    }


def _slstm_block(cfg, c: Creator, path):
    D = cfg.d_model
    W = D
    F = int(cfg.slstm_proj_factor * D)
    return {
        "ln": _norm(cfg, c, path + ("ln",)),
        "gates_in": {"w": c(path + ("gates_in", "w"), (D, 4 * W), ("embed", None), D)},
        "r": c(path + ("r",), (W, 4 * W), (None, None), W),
        "out_norm": _vec_norm(cfg, c, path + ("out_norm",), W),
        "ffn_up": {"w": c(path + ("ffn_up", "w"), (W, F), ("embed", "ffn"), W)},
        "ffn_down": {"w": c(path + ("ffn_down", "w"), (F, D), ("ffn", "embed"), F)},
    }


def _xattn_block(cfg, c: Creator, path):
    """Whisper decoder block: self-attn + cross-attn + FFN (LayerNorm, biases)."""
    return {
        "ln1": _norm(cfg, c, path + ("ln1",)),
        "self_attn": _gqa_attn(cfg, c, path + ("self_attn",)),
        "ln2": _norm(cfg, c, path + ("ln2",)),
        "cross_attn": _gqa_attn(cfg, c, path + ("cross_attn",)),
        "ln3": _norm(cfg, c, path + ("ln3",)),
        "mlp": _mlp(cfg, c, path + ("mlp",), bias=True),
    }


def _enc_block(cfg, c: Creator, path):
    return {
        "ln1": _norm(cfg, c, path + ("ln1",)),
        "attn": _gqa_attn(cfg, c, path + ("attn",)),
        "ln2": _norm(cfg, c, path + ("ln2",)),
        "mlp": _mlp(cfg, c, path + ("mlp",), bias=True),
    }


def _attn_ffn(cfg, c: Creator, path):
    return {
        "ln1": _norm(cfg, c, path + ("ln1",)),
        "attn": _gqa_attn(cfg, c, path + ("attn",)),
        "ln2": _norm(cfg, c, path + ("ln2",)),
        "mlp": _mlp(cfg, c, path + ("mlp",)),
    }


def _moe_attn_ffn(cfg, c: Creator, path):
    return {
        "ln1": _norm(cfg, c, path + ("ln1",)),
        "attn": _gqa_attn(cfg, c, path + ("attn",)),
        "ln2": _norm(cfg, c, path + ("ln2",)),
        "moe": _moe(cfg, c, path + ("moe",)),
    }


def _mla_ffn(cfg, c: Creator, path):
    return {
        "ln1": _norm(cfg, c, path + ("ln1",)),
        "attn": _mla_attn(cfg, c, path + ("attn",)),
        "ln2": _norm(cfg, c, path + ("ln2",)),
        "mlp": _mlp(cfg, c, path + ("mlp",)),
    }


def _mla_moe(cfg, c: Creator, path):
    return {
        "ln1": _norm(cfg, c, path + ("ln1",)),
        "attn": _mla_attn(cfg, c, path + ("attn",)),
        "ln2": _norm(cfg, c, path + ("ln2",)),
        "moe": _moe(cfg, c, path + ("moe",)),
    }


BLOCK_BUILDERS = {
    "attn_ffn": _attn_ffn,
    "moe_attn_ffn": _moe_attn_ffn,
    "mla_ffn": _mla_ffn,
    "mla_moe": _mla_moe,
    "griffin_rec": _griffin_rec,
    "griffin_attn": _griffin_attn,
    "mlstm": _mlstm_block,
    "slstm": _slstm_block,
    "xattn": _xattn_block,
    "enc": _enc_block,
}


# --------------------------------------------------------------------------
# Tree assembly
# --------------------------------------------------------------------------

def _stacked_creator(c: Creator, n: int) -> Creator:
    def sc(path, shape, logical, fan_in):
        return c(path, (n, *shape), ("layer", *logical), fan_in)
    return sc


def build_params(cfg: ModelConfig, creator: Creator) -> dict:
    cycle, n, tail = block_cycle(cfg)
    tree: dict = {
        "embed": {"w": creator(("embed", "w"), (cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), cfg.d_model)},
        "final_norm": _norm(cfg, creator, ("final_norm",)),
    }
    sc = _stacked_creator(creator, n)
    lead = lead_blocks(cfg)
    tree["blocks"] = {
        **({"lead": [BLOCK_BUILDERS[kind](cfg, creator, ("blocks", "lead", str(j), kind))
                     for j, kind in enumerate(lead)]} if lead else {}),
        "cycle": [BLOCK_BUILDERS[kind](cfg, sc, ("blocks", "cycle", str(j), kind))
                  for j, kind in enumerate(cycle)],
        "tail": [BLOCK_BUILDERS[kind](cfg, creator, ("blocks", "tail", str(j), kind))
                 for j, kind in enumerate(tail)],
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = {"w": creator(("lm_head", "w"), (cfg.d_model, cfg.vocab_size),
                                        ("embed", "vocab"), cfg.d_model)}
    if cfg.encoder_layers > 0:
        esc = _stacked_creator(creator, cfg.encoder_layers)
        tree["encoder"] = {
            "blocks": {"cycle": [_enc_block(cfg, esc, ("encoder", "blocks", "cycle", "0", "enc"))],
                       "tail": []},
            "final_norm": _norm(cfg, creator, ("encoder", "final_norm")),
        }
    return tree


# --------------------------------------------------------------------------
# Creators
# --------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig, dtype=None):
    dt = dtype or jnp.dtype(cfg.param_dtype)

    def c(path, shape, logical, fan_in):
        return jax.ShapeDtypeStruct(shape, dt)

    return build_params(cfg, c)


def param_logical_axes(cfg: ModelConfig):
    def c(path, shape, logical, fan_in):
        return tuple(logical)

    return build_params(cfg, c)


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=None):
    """Concrete init.  At published widths, jit it (``jax.jit(Model(cfg).init)``)
    so the weights are generated on the device in one program."""
    dt = dtype or jnp.dtype(cfg.param_dtype)
    counter = [0]

    def c(path, shape, logical, fan_in):
        counter[0] += 1
        key = jax.random.fold_in(rng, counter[0])
        if fan_in <= 0:  # biases / norm scales / gates
            name, parent = path[-1], path[-2] if len(path) > 1 else ""
            is_norm = parent.startswith("ln") or "norm" in parent
            if name == "w" and is_norm:
                # (1+w)-style RMSNorm (gemma) initialises w=0; plain norms w=1
                return jnp.zeros(shape, dt) if cfg.rms_offset else jnp.ones(shape, dt)
            if name == "lam":
                # RG-LRU: a in [0.9, 0.999] at init (Griffin appendix)
                u = jax.random.uniform(key, shape, jnp.float32, 0.9, 0.999)
                lam = jnp.log(jnp.expm1(-jnp.log(u) / 8.0))
                return lam.astype(jnp.float32)
            return jnp.zeros(shape, dt)
        std = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    tree = build_params(cfg, c)
    # norm weights default to ones (rms/ln scale)
    return tree


@functools.lru_cache(maxsize=512)
def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Total parameter count of what this device holds (its share of the
    experts); ``active_only`` counts each held expert by the share of tokens
    routed to it, top_k / num_experts (MoE active params for MODEL_FLOPS =
    6 * N_active * D).  Pure in the
    frozen config, so memoized — the simulator calls it on every report and
    sweeps call it per candidate."""
    total = [0]

    def c(path, shape, logical, fan_in):
        n = int(np.prod(shape))
        if active_only and "experts" in path:
            n = n * (cfg.top_k / cfg.num_experts)
        total[0] += n
        return None

    build_params(cfg, c)
    return int(total[0])
