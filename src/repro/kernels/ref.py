"""Pure-jnp oracle for the Pallas flash-attention kernel (the allclose target)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q: (B,H,Sq,D); k/v: (B,Hkv,Sk,D) -> (B,H,Sq,D)."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = H // Hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    qp = jnp.arange(Sq)[:, None]
    tp = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= tp <= qp
    if window > 0:
        mask &= tp > qp - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, vv.astype(jnp.float32)).astype(q.dtype)
