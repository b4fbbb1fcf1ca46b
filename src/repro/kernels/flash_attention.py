"""Flash-attention forward Pallas TPU kernel.

Online-softmax attention with explicit VMEM tiling: grid =
(batch, q_heads, num_q_blocks, num_kv_blocks); the innermost (kv) grid axis
is sequential on TPU, so fp32 scratch accumulators (m, l, acc) persist across
kv blocks and the output is written once at the last kv block.  GQA is
handled in the BlockSpec index map (kv head = q head // group), so K/V are
never materialised per-q-head.  Causal and sliding-window masks are applied
with iota comparisons against absolute positions.  Under a causal mask the kv
blocks wholly after a q block's last row are skipped: no compute, and their
index map repeats the last block needed, so no new copy into VMEM either.

Both products take the inputs' dtype on the MXU with fp32 accumulation; the
probabilities enter as two parts in V's dtype (head and remainder), so that
with bf16 inputs the second product is as precise as one in fp32.  The
softmax statistics are fp32.  V may have its own head width (MLA: 192-wide
q/k, 128-wide v).

Block sizes default to (128, 512) — q tile rows are a multiple of the 8-row
MXU subtile and kv tiles a multiple of the 128 lane dim; the (BQ, D) +
2*(BK, D) + (BQ, BK) working set stays well under the ~128 MB VMEM budget.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, window: int, scale: float, seq_q: int,
                  seq_k: int, block_q: int, block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki <= _last_kv_block(qi, causal, block_q, block_k, nk))
    def _step():
        q = q_ref[0, 0]                            # (BQ, D)
        k = k_ref[0, 0]                            # (BK, D)
        v = v_ref[0, 0]                            # (BK, Dv)
        # ragged tail blocks are padded with unspecified values: zero padded V
        # rows so 0-weight x garbage cannot poison the accumulator
        t_valid = (ki * block_k +
                   jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)) < seq_k
        v = jnp.where(t_valid, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (BQ, BK)

        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (q_pos < seq_q) & (k_pos < seq_k)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # floor the max so fully-masked (padded-q) rows give exp(-inf)=0, not NaN
        m_new = jnp.maximum(m_new, -1e30)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        # P enters the MXU in V's dtype as a head and a remainder, so a bf16
        # V gets products of float32 precision (V itself is exact)
        pv = functools.partial(jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        p_head = p.astype(v.dtype)
        o = pv(p_head, v)
        if v.dtype != jnp.float32:
            o += pv((p - p_head.astype(jnp.float32)).astype(v.dtype), v)
        acc_ref[...] = acc_ref[...] * corr + o
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                         jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _last_kv_block(qi, causal: bool, block_q: int, block_k: int, nk: int):
    """The last kv block a q block's rows attend to (all under no causal
    mask)."""
    if not causal:
        return nk - 1
    return jnp.minimum(((qi + 1) * block_q - 1) // block_k, nk - 1)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, D); k: (B, Hkv, Sk, D); v: (B, Hkv, Sk, Dv) with
    H % Hkv == 0.  Returns (B, H, Sq, Dv).  A causal mask puts q row i and
    key i at the same position."""
    B, H, Sq, D = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 128))
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, scale=scale,
        seq_q=Sq, seq_k=Sk, block_q=block_q, block_k=block_k)

    def kv(i, j):   # a skipped block repeats the last one needed: no new copy
        return jnp.minimum(j, _last_kv_block(i, causal, block_q, block_k, nk))

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // group, kv(i, j), 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, i, j: (b, h // group, kv(i, j), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, Dv), jnp.float32),  # output accum
        ],
        interpret=interpret,
    )(q, k, v)
