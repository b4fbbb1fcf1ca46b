"""Jitted public wrappers for the Pallas kernels.

Kernels compile to Mosaic for the TPU.  ``interpret=True`` runs them in the
Pallas interpreter instead (CPU correctness tests); it is an explicit
argument, never chosen from the backend.  The wrappers layout-adapt from the
model's (B, S, H, D) tensors to the kernels' (B, H, S, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, interpret: bool = False):
    """q: (B,H,Sq,D); k: (B,Hkv,Sk,D); v: (B,Hkv,Sk,Dv)."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window, scale=scale,
                               interpret=interpret)


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


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, kv_valid_len=None, *, interpret: bool = False):
    """q: (B,H,D); k/v: (B,Hkv,T,D)."""
    return _dec.decode_attention(q, k, v, kv_valid_len=kv_valid_len,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "offset", "interpret"))
def rmsnorm(x, w, *, eps: float = 1e-6, offset: bool = False,
            interpret: bool = False):
    return _rn.rmsnorm(x, w, eps=eps, offset=offset, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "offset", "interpret"))
def rmsnorm_residual(x, residual, w, *, eps: float = 1e-6, offset: bool = False,
                     interpret: bool = False):
    return _rn.rmsnorm(x, w, eps=eps, offset=offset, residual=residual,
                       interpret=interpret)
