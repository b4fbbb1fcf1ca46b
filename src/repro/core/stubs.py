"""Abstract attention primitive for simulator tracing.

When the simulator ingests a model it wants attention as ONE operator (the
paper traces at torch-op granularity where sdpa/flash-attention is a single
node), not as the score/softmax/value decomposition.  ``charon_attention``
is a JAX primitive with abstract evaluation only — simulation never executes
it; ``jax.make_jaxpr`` is enough.  A custom_vjp routes backward tracing to a
``charon_attention_bwd`` primitive.

The ingest passes :func:`attention_stub` to the model's block functions
(their ``attention`` argument) in place of ``layers.attention``.
"""
from __future__ import annotations

from functools import partial

import jax
from jax.extend import core as jex_core

attention_p = jex_core.Primitive("charon_attention")
attention_bwd_p = jex_core.Primitive("charon_attention_bwd")
attention_bwd_p.multiple_results = True


@attention_p.def_abstract_eval
def _attn_abs(q, k, v, *, causal, window):
    # q: (B, Sq, Hkv, G, Dq); v: (B, T, Hkv, Dv) -> (B, Sq, Hkv, G, Dv)
    return jax.core.ShapedArray((*q.shape[:-1], v.shape[-1]), q.dtype)


@attention_bwd_p.def_abstract_eval
def _attn_bwd_abs(q, k, v, ct, *, causal, window):
    return (jax.core.ShapedArray(q.shape, q.dtype),
            jax.core.ShapedArray(k.shape, k.dtype),
            jax.core.ShapedArray(v.shape, v.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attn(q, k, v, causal, window):
    return attention_p.bind(q, k, v, causal=causal, window=window)


def _attn_fwd(q, k, v, causal, window):
    return _attn(q, k, v, causal, window), (q, k, v)


def _attn_bwd(causal, window, res, ct):
    q, k, v = res
    return tuple(attention_bwd_p.bind(q, k, v, ct, causal=causal, window=window))


_attn.defvjp(_attn_fwd, _attn_bwd)


def attention_stub(q, k, v, *, q_offset=0, causal=True, window=0,
                   kv_valid_len=None, soft_cap=0.0, scale=None, flash=False):
    """``layers.attention``'s signature, traced as one abstract operator."""
    return _attn(q, k, v, causal, int(window))


def attention_flops(q_shape, v_shape, *, causal: bool, window: int) -> float:
    """2 matmuls over the (possibly windowed / causal) score matrix."""
    b, sq, hkv, g, dq = q_shape
    t, dv = v_shape[1], v_shape[-1]
    eff_t = min(t, window) if window else t
    frac = 0.5 if (causal and sq == t and not window) else 1.0
    return 2.0 * b * hkv * g * sq * eff_t * (dq + dv) * frac
