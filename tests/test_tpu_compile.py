"""Compiles for a TPU v5e that is described, not attached.

The chip's own compiler checks what interpret mode cannot: block shapes
against the tiling, fast-memory use, and whether a program fits the
device.  Nothing runs, so these tests say nothing about results or times.
The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker given this
file loads the TPU compiler.  The persistent compilation cache is off
around these compiles: an entry written for a described chip cannot be
read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import Model, layers as L, zero_cache

CFG = get_config("phi4-mini-3.8b")
HBM_BYTES = 16e9            # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, sharding, s.dtype), tree)


def test_flash_attention_compiles(one_chip):
    """phi4-mini's prefill attention at 2048 tokens, GQA 24/8 x 128, with
    the kernel tiles ``layers.attention`` uses."""
    Hkv, G, S, D = CFG.num_kv_heads, CFG.q_per_kv, 2048, CFG.head_dim
    compiled = ops.flash_attention_bshd.lower(
        _sds((1, S, Hkv, G, D), one_chip), _sds((1, S, Hkv, D), one_chip),
        _sds((1, S, Hkv, D), one_chip), causal=True,
        block_q=L.FLASH_Q, block_k=L.FLASH_KV).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mla_prefill_flash_attention_compiles(one_chip):
    """DeepSeek-V3's prefill attention at its published widths: 128 heads,
    q/k 192 wide, v 128, a 4096-token prompt, the tiles ``layers.attention``
    uses."""
    S, H = 4096, 128
    compiled = ops.flash_attention_bshd.lower(
        _sds((1, S, H, 1, 192), one_chip), _sds((1, S, H, 192), one_chip),
        _sds((1, S, H, 128), one_chip), causal=True, scale=0.1,
        block_q=L.FLASH_Q, block_k=L.FLASH_KV).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_phi4_decode_step_fits_one_chip(one_chip):
    """The served decode step at full depth: 4 slots x 2048 cache."""
    B, T = 4, 2048
    model = Model(CFG)
    params = _on(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    cache = _on(jax.eval_shape(lambda: zero_cache(CFG, B, T)), one_chip)
    tokens = _sds((B, 1), one_chip, jnp.int32)
    compiled = jax.jit(model.decode_step).lower(
        params, cache, {"tokens": tokens}).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 2 * CFG.param_count()  # weights + cache
    assert used < HBM_BYTES, used
    # the decode step runs no Pallas kernel
    assert "tpu_custom_call" not in compiled.as_text()
