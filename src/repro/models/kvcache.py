"""Decode-cache construction: concrete zeros, abstract specs, and shardings.

Cache layout mirrors ``params['blocks']`` (stacked over cycle repetitions so
``decode_step`` can scan over depth; the lead and the tail unstacked) plus a
per-slot ``pos``.

KV sharding policy (divisibility-aware, see DESIGN.md):
  * kv_heads % model-axis == 0  -> heads sharded (Megatron TP decode)
  * otherwise                   -> KV sequence sharded (flash-decode style)
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import axis_size
from repro.models.params import block_cycle, lead_blocks

CacheCreator = Callable[..., object]  # creator(shape, logical, dtype) -> leaf


def _kind_cache(cfg: ModelConfig, kind: str, c: CacheCreator, batch: int, cache_len: int):
    dt = jnp.dtype(cfg.dtype)
    Hkv, Dh = cfg.num_kv_heads, cfg.head_dim
    heads_ok = Hkv % axis_size("model") == 0
    kv_ax = ("batch", None, "kv_heads", "head_dim") if heads_ok else \
            ("batch", "kv_seq", None, "head_dim")

    def kv(T, H=Hkv, D=Dh):
        return {"k": c((batch, T, H, D), kv_ax, dt),
                "v": c((batch, T, H, D), kv_ax, dt)}

    if kind in ("attn_ffn", "moe_attn_ffn"):
        return kv(cache_len)
    if kind == "griffin_attn":
        return kv(min(cache_len, cfg.window) if cfg.window else cache_len)
    if kind in ("mla_moe", "mla_ffn"):
        return {"ckv": c((batch, cache_len, cfg.kv_lora_rank), ("batch", "kv_seq", None), dt),
                "kr": c((batch, cache_len, cfg.qk_rope_head_dim), ("batch", "kv_seq", None), dt)}
    if kind == "griffin_rec":
        W = cfg.lru_width or cfg.d_model
        return {"h": c((batch, W), ("batch", "lru_width"), dt),
                "conv": c((batch, cfg.conv_width - 1, W), ("batch", None, "lru_width"), dt)}
    if kind == "mlstm":
        H, D = cfg.num_heads, cfg.head_dim
        Di = int(cfg.mlstm_proj_factor * cfg.d_model)
        f32 = jnp.float32
        return {"conv": c((batch, cfg.conv_width - 1, Di), ("batch", None, "ffn"), dt),
                "C": c((batch, H, D, D), ("batch", None, None, None), f32),
                "n": c((batch, H, D), ("batch", None, None), f32),
                "m": c((batch, H), ("batch", None), f32)}
    if kind == "slstm":
        W = cfg.d_model
        f32 = jnp.float32
        return {k: c((batch, W), ("batch", None), f32) for k in ("c", "n", "h", "m")}
    if kind == "xattn":
        d = kv(cache_len, cfg.num_kv_heads, cfg.head_dim)
        d["ck"] = c((batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), kv_ax, dt)
        d["cv"] = c((batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), kv_ax, dt)
        return d
    raise ValueError(kind)


def build_cache(cfg: ModelConfig, creator: CacheCreator, batch: int, cache_len: int):
    cycle, n, tail = block_cycle(cfg)
    lead = lead_blocks(cfg)

    def stacked(shape, logical, dtype):
        return creator((n, *shape), ("layer", *logical), dtype)

    return {
        "blocks": {
            **({"lead": [_kind_cache(cfg, k, creator, batch, cache_len) for k in lead]}
               if lead else {}),
            "cycle": [_kind_cache(cfg, k, stacked, batch, cache_len) for k in cycle],
            "tail": [_kind_cache(cfg, k, creator, batch, cache_len) for k in tail],
        },
        "pos": creator((batch,), ("batch",), jnp.int32),
    }


def abstract_cache(cfg: ModelConfig, batch: int, cache_len: int):
    return build_cache(cfg, lambda s, l, d: jax.ShapeDtypeStruct(s, d), batch, cache_len)


def cache_logical_axes(cfg: ModelConfig, batch: int, cache_len: int):
    return build_cache(cfg, lambda s, l, d: tuple(l), batch, cache_len)


def zero_cache(cfg: ModelConfig, batch: int, cache_len: int):
    cache = build_cache(cfg, lambda s, l, d: jnp.zeros(s, d), batch, cache_len)
    cache["pos"] = jnp.full((batch,), cache_len, jnp.int32)  # cache "full" semantics
    return cache


@functools.lru_cache(maxsize=1024)
def cache_bytes(cfg: ModelConfig, batch: int, cache_len: int) -> int:
    """Total cache bytes; pure in (cfg, batch, cache_len) — the mesh-dependent
    sharding policy only picks logical axes, never shapes — so memoized for
    sweeps that query it per candidate."""
    total = [0]

    def c(s, l, d):
        total[0] += int(np.prod(s)) * jnp.dtype(d).itemsize
        return None

    build_cache(cfg, c, batch, cache_len)
    return total[0]
