"""Model assembly: one implementation covering all ten assigned architectures.

``Model`` exposes:
  * ``init(rng)``                          — concrete params (jit it at full width)
  * ``forward(params, batch)``             — full-sequence logits (train)
  * ``prefill(params, batch, cache_len)``  — logits + populated KV/state cache
  * ``decode_step(params, cache, batch)``  — one token with a seq_len cache

The decoder stack is ``lax.scan`` over block-cycle repetitions (stacked
params; see models/params.py) so HLO size is depth-independent.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import active_env, axis_size, logical_constraint as shard
from repro.models import layers as L
from repro.models.params import block_cycle, build_params, init_params, lead_blocks

Pytree = Any


def _heads_shardable(cfg: ModelConfig) -> bool:
    return cfg.num_kv_heads % axis_size("model") == 0


# ==========================================================================
# Attention blocks
# ==========================================================================

def _qkv(cfg, p, x, positions, *, rope=True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["q"]["w"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["k"]["w"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["v"]["w"].astype(x.dtype))
    if "b" in p["q"]:
        q = q + p["q"]["b"].astype(x.dtype)
        k = k + p["k"]["b"].astype(x.dtype)
        v = v + p["v"]["b"].astype(x.dtype)
    if rope:
        q = L.apply_rope(cfg, q, positions)
        k = L.apply_rope(cfg, k, positions)
    return q, k, v


def _attn_out(p, o, x_dtype):
    return jnp.einsum("bshk,hkd->bsd", o, p["o"]["w"].astype(x_dtype))


def _attn_shardings(cfg):
    """Megatron head-TP when kv heads divide the model axis; otherwise
    Ulysses-style context parallelism (q-sequence sharded, kv replicated)."""
    if _heads_shardable(cfg):
        q_ax = ("batch", "seq", "kv_heads", "q_per_kv", "head_dim")
        kv_ax = ("batch", "seq", "kv_heads", "head_dim")
    else:
        q_ax = ("batch", "seq_cp", "kv_heads", "q_per_kv", "head_dim")
        kv_ax = ("batch", None, "kv_heads", "head_dim")
    return q_ax, kv_ax


@L.named_scope("attn")
def gqa_full(cfg, p, x, positions, *, causal=True, window=0, rope=True):
    """Full-sequence GQA/MQA/MHA attention."""
    B, S, _ = x.shape
    Hkv, G, Dh = cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim
    q, k, v = _qkv(cfg, p, x, positions, rope=rope)
    q = q.reshape(B, S, Hkv, G, Dh)
    q_ax, kv_ax = _attn_shardings(cfg)
    q = shard(q, q_ax)
    k = shard(k, kv_ax)
    v = shard(v, kv_ax)
    # context-parallel runs keep q sequence-sharded -> single q block (no
    # python q loop crossing shard boundaries); TP runs use q blocks with
    # static causal truncation.
    q_block = S if not _heads_shardable(cfg) else 2048
    o = L.attention(q, k, v, q_offset=0, causal=causal, window=window, q_block=q_block,
                    kv_block=cfg.attn_kv_block,
                    score_dtype=jnp.dtype(cfg.attn_score_dtype))
    o = o.reshape(B, S, cfg.num_heads, Dh)
    return _attn_out(p, o, x.dtype), (k, v)


@L.named_scope("attn")
def gqa_decode(cfg, p, x, pos, cache, *, window=0, rope=True, positions=None):
    """Single-token attention against a per-slot ring cache {'k','v'}.

    ``pos``: (B,) int32 — per-sequence absolute position (continuous batching
    serves requests at different depths in one batch)."""
    B = x.shape[0]
    Hkv, G, Dh = cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim
    T = cache["k"].shape[1]
    if positions is None:
        positions = pos[:, None]
    q, k_new, v_new = _qkv(cfg, p, x, positions, rope=rope)
    q = q.reshape(B, 1, Hkv, G, Dh)
    slot = (pos % T).astype(jnp.int32)
    b_idx = jnp.arange(B)
    k = cache["k"].at[b_idx, slot].set(k_new[:, 0])
    v = cache["v"].at[b_idx, slot].set(v_new[:, 0])
    if _heads_shardable(cfg):
        kv_ax = ("batch", None, "kv_heads", "head_dim")
    else:
        kv_ax = ("batch", "kv_seq", None, "head_dim")
    k, v = shard(k, kv_ax), shard(v, kv_ax)
    valid = jnp.minimum(pos + 1, T)
    o = L.attention(q, k, v, q_offset=0, causal=False,
                    kv_valid_len=valid, strategy="dense")
    o = o.reshape(B, 1, cfg.num_heads, Dh)
    return _attn_out(p, o, x.dtype), {"k": k, "v": v}


@L.named_scope("attn")
def cross_full(cfg, p, x, enc_out):
    """Cross attention (whisper decoder): q from x, kv from encoder output."""
    B, S, _ = x.shape
    Hkv, G, Dh = cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["q"]["w"].astype(x.dtype))
    if "b" in p["q"]:
        q = q + p["q"]["b"].astype(x.dtype)
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["k"]["w"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["v"]["w"].astype(x.dtype))
    if "b" in p["k"]:
        k = k + p["k"]["b"].astype(x.dtype)
        v = v + p["v"]["b"].astype(x.dtype)
    q = q.reshape(B, S, Hkv, G, Dh)
    o = L.attention(q, k, v, q_offset=0, causal=False)
    return _attn_out(p, o.reshape(B, S, cfg.num_heads, Dh), x.dtype), (k, v)


@L.named_scope("attn")
def cross_decode(cfg, p, x, cache):
    B = x.shape[0]
    Hkv, G, Dh = cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["q"]["w"].astype(x.dtype))
    if "b" in p["q"]:
        q = q + p["q"]["b"].astype(x.dtype)
    q = q.reshape(B, 1, Hkv, G, Dh)
    o = L.attention(q, cache["ck"], cache["cv"], q_offset=0, causal=False,
                    strategy="dense")
    return _attn_out(p, o.reshape(B, 1, cfg.num_heads, Dh), x.dtype)


# --- MLA (deepseek) -------------------------------------------------------

MLA_Q_BLOCK, MLA_KV_BLOCK = 512, 512     # the flash kernel's tiles at prefill


@L.named_scope("attn")
def mla_full(cfg, p, x, positions, *, prefill: bool = False):
    """Expanded-form MLA for train/prefill; returns compressed cache parts.
    A ``prefill`` on one TPU attends with the flash kernel (forward only)."""
    B, S, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cq = L.rmsnorm(p["q_norm"]["w"], jnp.einsum("bsd,dr->bsr", x, p["dq"]["w"].astype(x.dtype)),
                   eps=cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["uq"]["w"].astype(x.dtype))      # (B,S,H,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(cfg, q_rope, positions)
    ckv = L.rmsnorm(p["kv_norm"]["w"], jnp.einsum("bsd,dr->bsr", x, p["dkv"]["w"].astype(x.dtype)),
                    eps=cfg.norm_eps)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["uk"]["w"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["uv"]["w"].astype(x.dtype))
    k_rope = L.apply_rope(cfg, jnp.einsum("bsd,dk->bsk", x, p["kr"]["w"].astype(x.dtype))[:, :, None, :],
                          positions)                                       # (B,S,1,dr)
    q_all = jnp.concatenate([q_nope, q_rope], -1).reshape(B, S, H, 1, dn + dr)
    k_all = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], -1)
    q_all = shard(q_all, ("batch", "seq", "heads", None, "head_dim"))
    k_all = shard(k_all, ("batch", "seq", "heads", "head_dim"))
    v = shard(v, ("batch", "seq", "heads", "head_dim"))
    # 128 heads make the float32 scores' passes through HBM the XLA path's
    # largest cost at prefill; on one TPU the flash kernel keeps them in VMEM.
    tiles = {}
    if prefill and jax.default_backend() == "tpu" and active_env() is None:
        tiles = dict(strategy="pallas", q_block=MLA_Q_BLOCK, kv_block=MLA_KV_BLOCK)
    o = L.attention(q_all, k_all, v, q_offset=0, causal=True,
                    scale=L.attn_scale(cfg) / math.sqrt(dn + dr),
                    score_dtype=jnp.dtype(cfg.attn_score_dtype), **tiles)
    o = o.reshape(B, S, H, dv)
    return _attn_out(p, o, x.dtype), (ckv, k_rope[:, :, 0, :])


@L.named_scope("attn")
def mla_decode(cfg, p, x, pos, cache):
    """Absorbed-form MLA decode on the compressed (c_kv, k_rope) cache."""
    B = x.shape[0]
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    T = cache["ckv"].shape[1]
    positions = pos[:, None]
    cq = L.rmsnorm(p["q_norm"]["w"], jnp.einsum("bsd,dr->bsr", x, p["dq"]["w"].astype(x.dtype)),
                   eps=cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["uq"]["w"].astype(x.dtype))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(cfg, q_rope, positions)
    # absorb W_uk: q_c[h] = q_nope[h] @ W_uk[h]^T  -> latent space
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["uk"]["w"].astype(x.dtype))
    ckv_new = L.rmsnorm(p["kv_norm"]["w"], jnp.einsum("bsd,dr->bsr", x, p["dkv"]["w"].astype(x.dtype)),
                        eps=cfg.norm_eps)
    kr_new = L.apply_rope(cfg, jnp.einsum("bsd,dk->bsk", x, p["kr"]["w"].astype(x.dtype))[:, :, None, :],
                          positions)[:, :, 0, :]
    slot = (pos % T).astype(jnp.int32)
    b_idx = jnp.arange(B)
    ckv = cache["ckv"].at[b_idx, slot].set(ckv_new[:, 0])
    kr = cache["kr"].at[b_idx, slot].set(kr_new[:, 0])
    ckv = shard(ckv, ("batch", "kv_seq", None))
    kr = shard(kr, ("batch", "kv_seq", None))
    scale = L.attn_scale(cfg) / math.sqrt(dn + dr)
    s = (jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32), ckv.astype(jnp.float32))
         + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32), kr.astype(jnp.float32))) * scale
    valid = jnp.minimum(pos + 1, T)
    s = jnp.where(jnp.arange(T)[None, None, None, :] < valid[:, None, None, None], s, L.NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", pr, ckv.astype(jnp.float32)).astype(x.dtype)
    o = jnp.einsum("bshr,rhk->bshk", o_lat, p["uv"]["w"].astype(x.dtype))  # (B,1,H,dv)
    return _attn_out(p, o, x.dtype), {"ckv": ckv, "kr": kr}


# ==========================================================================
# Block dispatch — full-sequence mode
# ==========================================================================

@L.named_scope()
def apply_block_full(cfg, kind, p, h, aux, collect_cache):
    """Returns (h, cache_out_or_None, aux_loss)."""
    h = shard(h, ("batch", "seq_sp", "embed"))   # Megatron-SP residual stream
    positions = aux["positions"]
    zero = jnp.zeros((), jnp.float32)
    cache_len = aux.get("cache_len", 0)

    def kv_cache(k, v, window=0):
        if not collect_cache:
            return None
        T = min(cache_len, window) if window else cache_len
        S = k.shape[1]
        kc = jnp.zeros((k.shape[0], T, *k.shape[2:]), k.dtype)
        vc = jnp.zeros_like(kc)
        if window and S > T:
            k, v = k[:, -T:], v[:, -T:]
            S = T
        kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
        return {"k": kc, "v": vc}

    if kind == "attn_ffn":
        a, (k, v) = gqa_full(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], h), positions)
        h = h + a
        h = h + L.ffn(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
        return h, kv_cache(k, v), zero

    if kind in ("moe_attn_ffn", "mla_moe", "mla_ffn"):
        y = L.apply_norm(cfg, p["ln1"], h)
        if kind == "moe_attn_ffn":
            a, (k, v) = gqa_full(cfg, p["attn"], y, positions)
        else:
            a, (ckv, kr) = mla_full(cfg, p["attn"], y, positions, prefill=collect_cache)
        h = h + a
        y = L.apply_norm(cfg, p["ln2"], h)
        if kind == "mla_ffn":
            m, aux_loss = L.ffn(cfg, p["mlp"], y), zero
        else:
            m, aux_loss = L.moe_ffn(cfg, p["moe"], y)
        h = h + m
        if kind == "moe_attn_ffn":
            return h, kv_cache(k, v), aux_loss
        cache = None
        if collect_cache:
            T = cache_len
            ckv_c = jnp.zeros((ckv.shape[0], T, ckv.shape[2]), ckv.dtype)
            kr_c = jnp.zeros((kr.shape[0], T, kr.shape[2]), kr.dtype)
            ckv_c = jax.lax.dynamic_update_slice(ckv_c, ckv, (0, 0, 0))
            kr_c = jax.lax.dynamic_update_slice(kr_c, kr, (0, 0, 0))
            cache = {"ckv": ckv_c, "kr": kr_c}
        return h, cache, aux_loss

    if kind == "griffin_attn":
        a, (k, v) = gqa_full(cfg, p["attn"], L.apply_norm(cfg, p["ln"], h), positions,
                             window=cfg.window)
        h = h + a
        h = h + L.ffn(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
        return h, kv_cache(k, v, window=cfg.window), zero

    if kind == "griffin_rec":
        y = L.apply_norm(cfg, p["ln"], h)
        g = jax.nn.gelu(L.linear(p["in_gate"], y), approximate=True)
        r = L.linear(p["in_rec"], y)
        r = shard(r, ("batch", "seq", "lru_width"))
        r, conv_state = L.causal_conv1d(p["conv"], r, None)
        r, h_last = L.rglru_scan(p["rglru"], r, None)
        h = h + L.linear(p["out"], g * r)
        h = h + L.ffn(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
        cache = {"h": h_last.astype(h.dtype), "conv": conv_state} if collect_cache else None
        return h, cache, zero

    if kind == "mlstm":
        B, S, D = h.shape
        H, Dh = cfg.num_heads, cfg.head_dim
        y = L.apply_norm(cfg, p["ln"], h)
        u = L.linear(p["up"], y)
        cv, conv_state = L.causal_conv1d(p["conv"], u, None)
        c = jax.nn.silu(cv)
        q = L.linear(p["q"], c).reshape(B, S, H, Dh)
        k = L.linear(p["k"], c).reshape(B, S, H, Dh)
        v = L.linear(p["v"], u).reshape(B, S, H, Dh)
        gates = L.linear(p["gates"], c)
        i_g, f_g = gates[..., :H], gates[..., H:]
        yc, state = L.mlstm_chunkwise(q, k, v, i_g, f_g, chunk=cfg.chunk_size)
        yn = L.rmsnorm(p["out_norm"]["w"], yc.reshape(B, S, H * Dh), eps=cfg.norm_eps)
        out = yn * jax.nn.silu(L.linear(p["z"], y))
        h = h + L.linear(p["o"], out)
        cache = None
        if collect_cache:
            C, n, m = state
            cache = {"conv": conv_state, "C": C.astype(jnp.float32), "n": n, "m": m}
        return h, cache, zero

    if kind == "slstm":
        y = L.apply_norm(cfg, p["ln"], h)
        g_in = L.linear(p["gates_in"], y)
        hs, state = L.slstm_scan(p, g_in, None)
        hn = L.rmsnorm(p["out_norm"]["w"], hs, eps=cfg.norm_eps)
        ff = L.linear(p["ffn_down"], jax.nn.gelu(L.linear(p["ffn_up"], hn), approximate=True))
        h = h + ff
        cache = {"c": state[0], "n": state[1], "h": state[2], "m": state[3]} if collect_cache else None
        return h, cache, zero

    if kind == "xattn":
        a, (k, v) = gqa_full(cfg, p["self_attn"], L.apply_norm(cfg, p["ln1"], h), positions,
                             rope=False)
        h = h + a
        ca, (ck, cv) = cross_full(cfg, p["cross_attn"], L.apply_norm(cfg, p["ln2"], h),
                                  aux["enc_out"])
        h = h + ca
        h = h + L.ffn(cfg, p["mlp"], L.apply_norm(cfg, p["ln3"], h))
        cache = None
        if collect_cache:
            cache = kv_cache(k, v)
            cache["ck"], cache["cv"] = ck, cv
        return h, cache, zero

    if kind == "enc":
        a, _ = gqa_full(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], h), positions,
                        causal=False, rope=False)
        h = h + a
        h = h + L.ffn(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
        return h, None, zero

    raise ValueError(kind)


# ==========================================================================
# Block dispatch — decode mode
# ==========================================================================

@L.named_scope()
def apply_block_decode(cfg, kind, p, h, cache, aux):
    """Returns (h, new_cache)."""
    pos = aux["pos"]
    positions = aux.get("decode_positions")

    if kind == "attn_ffn":
        a, c = gqa_decode(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], h), pos, cache,
                          positions=positions)
        h = h + a
        h = h + L.ffn(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
        return h, c

    if kind == "moe_attn_ffn":
        a, c = gqa_decode(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], h), pos, cache)
        h = h + a
        m, _ = L.moe_ffn(cfg, p["moe"], L.apply_norm(cfg, p["ln2"], h))
        return h + m, c

    if kind in ("mla_moe", "mla_ffn"):
        a, c = mla_decode(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], h), pos, cache)
        h = h + a
        y = L.apply_norm(cfg, p["ln2"], h)
        m = L.ffn(cfg, p["mlp"], y) if kind == "mla_ffn" else L.moe_ffn(cfg, p["moe"], y)[0]
        return h + m, c

    if kind == "griffin_attn":
        a, c = gqa_decode(cfg, p["attn"], L.apply_norm(cfg, p["ln"], h), pos, cache,
                          window=cfg.window)
        h = h + a
        h = h + L.ffn(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
        return h, c

    if kind == "griffin_rec":
        y = L.apply_norm(cfg, p["ln"], h)
        g = jax.nn.gelu(L.linear(p["in_gate"], y), approximate=True)
        r = L.linear(p["in_rec"], y)
        r, conv_state = L.causal_conv1d(p["conv"], r, cache["conv"])
        r_t, h_state = L.rglru_step(p["rglru"], r[:, 0], cache["h"])
        h = h + L.linear(p["out"], g * r_t[:, None, :])
        h = h + L.ffn(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
        return h, {"h": h_state.astype(h.dtype), "conv": conv_state}

    if kind == "mlstm":
        B = h.shape[0]
        H, Dh = cfg.num_heads, cfg.head_dim
        y = L.apply_norm(cfg, p["ln"], h)
        u = L.linear(p["up"], y)
        cv, conv_state = L.causal_conv1d(p["conv"], u, cache["conv"])
        c = jax.nn.silu(cv)
        q = L.linear(p["q"], c).reshape(B, H, Dh)
        k = L.linear(p["k"], c).reshape(B, H, Dh)
        v = L.linear(p["v"], u).reshape(B, H, Dh)
        gates = L.linear(p["gates"], c)[:, 0]
        i_g, f_g = gates[..., :H], gates[..., H:]
        yc, (C, n, m) = L.mlstm_step(q, k, v, i_g, f_g, (cache["C"], cache["n"], cache["m"]))
        yn = L.rmsnorm(p["out_norm"]["w"], yc.reshape(B, 1, H * Dh), eps=cfg.norm_eps)
        out = yn * jax.nn.silu(L.linear(p["z"], y))
        h = h + L.linear(p["o"], out)
        return h, {"conv": conv_state, "C": C, "n": n, "m": m}

    if kind == "slstm":
        y = L.apply_norm(cfg, p["ln"], h)
        g_in = L.linear(p["gates_in"], y)
        state = (cache["c"], cache["n"], cache["h"], cache["m"])
        hs, state = L.slstm_scan(p, g_in, state)
        hn = L.rmsnorm(p["out_norm"]["w"], hs, eps=cfg.norm_eps)
        ff = L.linear(p["ffn_down"], jax.nn.gelu(L.linear(p["ffn_up"], hn), approximate=True))
        h = h + ff
        return h, {"c": state[0], "n": state[1], "h": state[2], "m": state[3]}

    if kind == "xattn":
        self_cache = {"k": cache["k"], "v": cache["v"]}
        a, c = gqa_decode(cfg, p["self_attn"], L.apply_norm(cfg, p["ln1"], h), pos,
                          self_cache, rope=False)
        h = h + a
        h = h + cross_decode(cfg, p["cross_attn"], L.apply_norm(cfg, p["ln2"], h), cache)
        h = h + L.ffn(cfg, p["mlp"], L.apply_norm(cfg, p["ln3"], h))
        return h, {"k": c["k"], "v": c["v"], "ck": cache["ck"], "cv": cache["cv"]}

    raise ValueError(kind)


# ==========================================================================
# Model facade
# ==========================================================================

class Model:
    def __init__(self, cfg: ModelConfig, *, remat_policy: str = "none"):
        self.cfg = cfg
        self.remat_policy = remat_policy
        self.lead = lead_blocks(cfg)
        self.cycle, self.n_cycles, self.tail = block_cycle(cfg)

    # ---- params ----
    def init(self, rng: jax.Array) -> Pytree:
        return init_params(self.cfg, rng)

    # ---- embedding / head ----
    @L.named_scope("embed")
    def _embed(self, params, tokens, positions, batch):
        cfg = self.cfg
        h = jnp.take(params["embed"]["w"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
        if cfg.scale_embedding:
            h = h * math.sqrt(cfg.d_model)
        if cfg.rope_style == "none":
            pos2d = positions if positions.ndim == 2 else positions[..., 0]
            h = h + L.sinusoidal_positions(pos2d, cfg.d_model).astype(h.dtype)
        if cfg.frontend == "vision_patches" and batch.get("patch_embeds") is not None:
            pe = batch["patch_embeds"].astype(h.dtype)
            h = jax.lax.dynamic_update_slice(h, pe, (0, 0, 0))
        return shard(h, ("batch", "seq_sp", "embed"))

    @L.named_scope("head")
    def _logits(self, params, h):
        cfg = self.cfg
        w = params["embed"]["w"].T if cfg.tie_embeddings else params["lm_head"]["w"]
        logits = jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype)).astype(jnp.float32)
        # seq-sharded logits (full local vocab) -> local per-token CE; decode
        # (S=1) falls through to vocab sharding via divisibility resolution.
        return shard(logits, ("batch", "seq_sp", "vocab"))

    # ---- encoder (whisper) ----
    def encode(self, params, frame_embeds):
        cfg = self.cfg
        h = frame_embeds.astype(jnp.dtype(cfg.dtype))
        B, S, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        h = h + L.sinusoidal_positions(positions, cfg.d_model).astype(h.dtype)
        aux = {"positions": positions}

        def body(carry, p_slice):
            hh = carry
            hh, _, _ = apply_block_full(cfg, "enc", p_slice[0], hh, aux, False)
            return hh, None

        body_fn = self._maybe_remat(body)
        h, _ = jax.lax.scan(body_fn, h, params["encoder"]["blocks"]["cycle"])
        return L.apply_norm(cfg, params["encoder"]["final_norm"], h)

    def _maybe_remat(self, fn):
        if self.remat_policy == "block":
            return jax.checkpoint(fn)
        if self.remat_policy == "dots":
            return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
        return fn

    # ---- full-sequence stack ----
    def _run_stack(self, params, h, aux, collect_cache):
        """The lead blocks, the scanned cycle, then the tail.  Returns
        (h, aux_loss, caches by group as ``params['blocks']`` has them)."""
        cfg = self.cfg
        cycle = self.cycle
        aux_loss = jnp.zeros((), jnp.float32)

        def unrolled(group, kinds):
            nonlocal h, aux_loss
            caches = []
            for j, kind in enumerate(kinds):
                h, c_out, al = apply_block_full(cfg, kind, params["blocks"][group][j], h, aux,
                                                collect_cache)
                caches.append(c_out)
                aux_loss = aux_loss + al
            return caches

        caches = {"lead": unrolled("lead", self.lead)} if self.lead else {}

        def body(carry, xs):
            hh, aux_acc = carry
            cache_outs = []
            for j, kind in enumerate(cycle):
                hh, c_out, al = apply_block_full(cfg, kind, xs[j], hh, aux, collect_cache)
                cache_outs.append(c_out)
                aux_acc = aux_acc + al
            return (hh, aux_acc), (cache_outs if collect_cache else None)

        body_fn = self._maybe_remat(body)
        (h, aux_loss), caches["cycle"] = jax.lax.scan(
            body_fn, (h, aux_loss), params["blocks"]["cycle"])
        caches["tail"] = unrolled("tail", self.tail)
        return h, aux_loss, caches

    # ---- public entry points ----
    def forward(self, params, batch):
        """Full-sequence forward.  batch: tokens (B,S)[, positions, frame_embeds,
        patch_embeds].  Returns (logits, aux_loss)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        aux = {"positions": positions}
        if cfg.encoder_layers > 0:
            aux["enc_out"] = self.encode(params, batch["frame_embeds"])
        h = self._embed(params, tokens, positions, batch)
        h, aux_loss, _ = self._run_stack(params, h, aux, collect_cache=False)
        h = L.apply_norm(cfg, params["final_norm"], h)
        return self._logits(params, h), aux_loss

    def prefill(self, params, batch, cache_len: int):
        """Full-sequence forward that also populates a decode cache."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        aux = {"positions": positions, "cache_len": cache_len}
        if cfg.encoder_layers > 0:
            aux["enc_out"] = self.encode(params, batch["frame_embeds"])
        h = self._embed(params, tokens, positions, batch)
        h, aux_loss, caches = self._run_stack(params, h, aux, collect_cache=True)
        h = L.apply_norm(cfg, params["final_norm"], h)
        logits = self._logits(params, h[:, -1:])
        cache = {"blocks": caches, "pos": jnp.full((B,), S, jnp.int32)}
        return logits, cache

    def decode_step(self, params, cache, batch):
        """One-token decode.  batch: tokens (B,1)[, positions (B,1[,3])].
        Returns (logits, new_cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B = tokens.shape[0]
        pos = cache["pos"]                    # (B,) per-slot positions
        positions = batch.get("positions")
        if positions is None:
            positions = pos[:, None]
        aux = {"pos": pos, "decode_positions": positions}
        h = self._embed(params, tokens, positions, batch)
        cycle = self.cycle

        def unrolled(group, kinds):
            nonlocal h
            caches = []
            for j, kind in enumerate(kinds):
                h, cj = apply_block_decode(cfg, kind, params["blocks"][group][j], h,
                                           cache["blocks"][group][j], aux)
                caches.append(cj)
            return caches

        caches = {"lead": unrolled("lead", self.lead)} if self.lead else {}

        def body(hh, xs):
            p_slice, c_slice = xs
            new_c = []
            for j, kind in enumerate(cycle):
                hh, cj = apply_block_decode(cfg, kind, p_slice[j], hh, c_slice[j], aux)
                new_c.append(cj)
            return hh, new_c

        h, caches["cycle"] = jax.lax.scan(
            body, h, (params["blocks"]["cycle"], cache["blocks"]["cycle"]))
        caches["tail"] = unrolled("tail", self.tail)
        h = L.apply_norm(cfg, params["final_norm"], h)
        logits = self._logits(params, h)
        new_cache = {"blocks": caches, "pos": pos + 1}
        return logits, new_cache
