"""Jitted public wrapper for the Pallas flash-attention kernel.

The kernel compiles to Mosaic for the TPU.  ``interpret=True`` runs it in
the Pallas interpreter instead (CPU correctness tests); it is an explicit
argument, never chosen from the backend.  The wrapper layout-adapts from the
model's (B, S, H, D) tensors to the kernel's (B, H, S, D).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention_bshd(q, k, v, *, causal: bool = True, window: int = 0,
                         scale: float | None = None, block_q: int = _fa.DEFAULT_BLOCK_Q,
                         block_k: int = _fa.DEFAULT_BLOCK_K, interpret: bool = False):
    """Model layout: q (B,S,Hkv,G,D); k (B,T,Hkv,D); v (B,T,Hkv,Dv)
    -> (B,S,Hkv,G,Dv)."""
    B, S, Hkv, G, D = q.shape
    qh = q.reshape(B, S, Hkv * G, D).transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    o = _fa.flash_attention(qh, kh, vh, causal=causal, window=window, scale=scale,
                            block_q=block_q, block_k=block_k, interpret=interpret)
    return o.transpose(0, 2, 1, 3).reshape(B, S, Hkv, G, v.shape[-1])
