"""The Pallas flash kernel vs pure-jnp oracles: shape/dtype sweeps (interpret
mode); and which attention implementation layers.attention chooses."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as fa_raw


def _qkv(B, H, Hkv, Sq, Sk, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D), jnp.float32).astype(dtype)
    return q, k, v


FA_CASES = [
    # (B, H, Hkv, Sq, Sk, D, causal, window, dtype, tol)
    (1, 2, 2, 128, 128, 64, True, 0, jnp.float32, 2e-6),
    (2, 4, 2, 192, 192, 64, True, 0, jnp.float32, 2e-6),   # GQA + ragged blocks
    (1, 4, 1, 128, 256, 32, False, 0, jnp.float32, 2e-6),  # MQA cross
    (2, 2, 2, 160, 160, 64, True, 64, jnp.float32, 2e-6),  # sliding window
    (1, 2, 2, 128, 128, 128, True, 0, jnp.bfloat16, 2e-2),
    (1, 8, 4, 96, 96, 64, True, 0, jnp.bfloat16, 2e-2),
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_sweep(case):
    B, H, Hkv, Sq, Sk, D, causal, window, dtype, tol = case
    q, k, v = _qkv(B, H, Hkv, Sq, Sk, D, dtype)
    out = fa_raw(q, k, v, causal=causal, window=window, interpret=True,
                 block_q=64, block_k=128)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
def test_flash_attention_value_width(dtype, tol):
    """MLA's shapes: v narrower than q/k, a scale of its own, and causal kv
    blocks skipped (q blocks of 32 rows against kv blocks of 64)."""
    q, k, _ = _qkv(1, 4, 4, 256, 256, 48, dtype)
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 256, 32), jnp.float32).astype(dtype)
    out = fa_raw(q, k, v, causal=True, scale=0.3, interpret=True, block_q=32, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True, scale=0.3)
    assert out.shape == (1, 4, 256, 32)
    np.testing.assert_allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                               atol=tol, rtol=tol)


# (sq, t, heads, flash, backend, model_axis) -> (implementation, q tile, kv tile):
# one case per call site of layers.attention
PLAN_CASES = {
    "decode with a valid length": ((1, 2048, 8, False, "tpu", None), ("dense", None, None)),
    "one query row, no valid length": ((1, 1500, 20, False, "cpu", None), ("dense", None, None)),
    "phi4 prefill at 896": ((896, 896, 8, False, "tpu", None), ("dense", None, None)),
    "T = 1152": ((1152, 1152, 8, False, "tpu", None), ("blockwise", 2048, 512)),
    "train at 2048": ((2048, 2048, 8, False, "tpu", None), ("blockwise", 2048, 512)),
    "context parallel": ((4096, 4096, 8, False, "cpu", 16), ("blockwise", 4096, 512)),
    "cross attention on a mesh": ((4096, 1500, 20, False, "cpu", 16), ("blockwise", 2048, 512)),
    "MLA prefill on one TPU": ((4096, 4096, 128, True, "tpu", None), ("flash", 512, 512)),
    "MLA prefill under a mesh": ((4096, 4096, 128, True, "tpu", 1), ("blockwise", 2048, 512)),
    "MLA train forward on a TPU": ((4096, 4096, 128, False, "tpu", None), ("blockwise", 2048, 512)),
    "MLA prefill on the CPU": ((1024, 1024, 128, True, "cpu", None), ("dense", None, None)),
}


@pytest.mark.parametrize("case", PLAN_CASES)
def test_attention_plan(case):
    """layers.attention_plan alone chooses the implementation and its tiles,
    from the shapes, the backend and the mesh."""
    from repro.models import layers as L
    (sq, t, heads, flash, backend, model_axis), want = PLAN_CASES[case]
    assert L.attention_plan(sq, t, heads, flash=flash, backend=backend,
                            model_axis=model_axis) == want


def test_flash_matches_model_layout():
    """bshd wrapper agrees with the model's blockwise attention path."""
    from repro.models import layers as L
    B, S, Hkv, G, D = 2, 128, 2, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, Hkv, G, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D))
    out = ops.flash_attention_bshd(q, k, v, causal=True, interpret=True)
    want = L.attend_blockwise(q, k, v, q_offset=0, causal=True,
                              q_block=64, kv_block=64)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_flash_matches_model_layout_for_mla():
    """The bshd wrapper on MLA's layout (every head its own K and V, v
    narrower than q/k, a scale of its own) agrees with the blockwise path."""
    from repro.models import layers as L
    B, S, H, Dq, Dv = 1, 256, 4, 48, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, H, 1, Dq))
    k = jax.random.normal(ks[1], (B, S, H, Dq))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    out = ops.flash_attention_bshd(q, k, v, causal=True, scale=0.2, block_q=64,
                                   block_k=64, interpret=True)
    want = L.attend_blockwise(q, k, v, q_offset=0, causal=True, scale=0.2,
                              q_block=64, kv_block=64)
    assert out.shape == (B, S, H, 1, Dv)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
