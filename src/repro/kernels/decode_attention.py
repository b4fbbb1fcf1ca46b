"""Flash-decode (split-KV) Pallas TPU kernel.

Single-token decode attends a (B, Hkv, T, D) cache.  The KV sequence splits
across the grid; every split writes a partial (m, l, o) triple; a cheap jnp
combine merges the partials (log-sum-exp reduction).  This is the
FlashDecoding split-K adaptation for TPU: the long T axis becomes grid
parallelism instead of one long sequential scan, keeping the MXU fed at
batch=1 decode shapes.  Ring caches pass ``kv_valid_len`` to mask dead slots;
it reaches the kernel as a scalar-prefetch operand in SMEM (the TPU compiler
refuses a rank-1 VMEM block of one element per sequence).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

DEFAULT_BLOCK_T = 1024


def _decode_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                   scale: float, block_t: int, seq_t: int, group: int):
    bi = pl.program_id(0)
    si = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)              # (G, D) — q heads of this kv head
    k = k_ref[0, 0].astype(jnp.float32)              # (BT, D)
    v = v_ref[0, 0].astype(jnp.float32)
    tv = (si * block_t +
          jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)) < seq_t
    v = jnp.where(tv, v, 0.0)                        # sanitize padded rows
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, BT)
    t_pos = si * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = valid_ref[bi]
    s = jnp.where((t_pos < seq_t) & (t_pos < valid), s, NEG_INF)
    m = jnp.maximum(s.max(axis=1, keepdims=True), -1e30)   # (G, 1)
    p = jnp.exp(s - m)
    l = p.sum(axis=1, keepdims=True)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))  # (G, D)
    o_ref[0, 0, 0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     kv_valid_len: jax.Array | None = None,
                     scale: float | None = None,
                     block_t: int = DEFAULT_BLOCK_T,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D) one token per sequence; k/v: (B, Hkv, T, D).
    Returns (B, H, D)."""
    B, H, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_t = min(block_t, max(T, 128))
    ns = pl.cdiv(T, block_t)
    if kv_valid_len is None:
        kv_valid_len = jnp.full((B,), T, jnp.int32)
    qg = q.reshape(B, Hkv, group, D)

    kernel = functools.partial(_decode_kernel, scale=scale, block_t=block_t,
                               seq_t=T, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, group, D), lambda b, h, s, vl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_t, D), lambda b, h, s, vl: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_t, D), lambda b, h, s, vl: (b, h, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, group, D), lambda b, h, s, vl: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, group, 1), lambda b, h, s, vl: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, group, 1), lambda b, h, s, vl: (b, h, s, 0, 0)),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, ns, group, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, ns, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, ns, group, 1), jnp.float32),
        ],
        interpret=interpret,
    )(kv_valid_len.astype(jnp.int32), qg, k, v)

    # combine splits: weighted by l * exp(m - m_max)
    m_part, l_part = m_part[..., 0], l_part[..., 0]
    m_max = m_part.max(axis=2, keepdims=True)                    # (B,Hkv,1,G)
    w = l_part * jnp.exp(m_part - m_max)                         # (B,Hkv,S,G)
    denom = jnp.maximum(w.sum(axis=2), 1e-30)                    # (B,Hkv,G)
    o = (o_part * w[..., None]).sum(axis=2) / denom[..., None]
    return o.reshape(B, H, D).astype(q.dtype)
