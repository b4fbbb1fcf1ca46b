"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, asserting output shapes + finiteness; prefill/decode consistency."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, get_shape, get_tiny_config, supports_shape
from repro.models import Model, count_params
from repro.training.optimizer import make_optimizer
from repro.training.train_step import make_train_step
from repro.configs.base import RunConfig, ShapeConfig


def _batch(cfg, B, S, *, labels=False, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0, cfg.vocab_size)
    b = {"tokens": toks}
    if labels:
        b["labels"] = jnp.roll(toks, -1, axis=1)
    if cfg.rope_style == "mrope":
        b["positions"] = jnp.broadcast_to(jnp.arange(S)[None, :, None], (B, S, 3)).astype(jnp.int32)
    if cfg.encoder_layers:
        b["frame_embeds"] = jax.random.normal(jax.random.PRNGKey(2), (B, cfg.encoder_seq, cfg.d_model)) * 0.1
    if cfg.frontend == "vision_patches":
        b["patch_embeds"] = jax.random.normal(jax.random.PRNGKey(3), (B, 8, cfg.d_model)) * 0.1
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_finite(arch):
    cfg = get_tiny_config(arch)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 24
    logits, aux = m.forward(params, _batch(cfg, B, S))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_decreases_nothing_nan(arch):
    cfg = get_tiny_config(arch)
    run = RunConfig(model=cfg, shape=ShapeConfig("t", 16, 2, "train"))
    opt = make_optimizer("adamw", peak_lr=1e-3)
    step = jax.jit(make_train_step(cfg, run, opt))
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    state = {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    batch = _batch(cfg, 2, 16, labels=True)
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(state["step"]) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_match_forward(arch):
    cfg = get_tiny_config(arch)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    full = _batch(cfg, B, S + 1)
    pre = {k: (v[:, :S] if v.ndim >= 2 and v.shape[1] == S + 1 else v)
           for k, v in full.items()}
    lf, _ = m.forward(params, full)
    lp, cache = m.prefill(params, pre, cache_len=S + 4)
    db = {"tokens": full["tokens"][:, S:S + 1]}
    if cfg.rope_style == "mrope":
        db["positions"] = jnp.full((B, 1, 3), S, jnp.int32)
    ld, cache2 = m.decode_step(params, cache, db)
    tol = 0.08  # bf16 absorbed-vs-expanded MLA reordering
    assert float(jnp.max(jnp.abs(lp - lf[:, S - 1:S]))) < tol
    assert float(jnp.max(jnp.abs(ld - lf[:, S:S + 1]))) < tol
    assert int(cache2["pos"][0]) == S + 1


def test_param_counts_full_configs():
    """Exact configs instantiate abstractly and land in the right ballpark."""
    expect = {
        "qwen2.5-32b": (31e9, 34e9),
        "phi4-mini-3.8b": (3.2e9, 4.4e9),
        "gemma-7b": (8.0e9, 9.5e9),
        "yi-34b": (33e9, 36e9),
        "deepseek-v3-671b": (640e9, 720e9),
        "olmoe-1b-7b": (6.5e9, 7.5e9),
        "recurrentgemma-9b": (8.5e9, 11.5e9),
        "qwen2-vl-7b": (7e9, 8.5e9),
        "whisper-large-v3": (1.4e9, 1.9e9),
        "xlstm-125m": (0.10e9, 0.18e9),
    }
    for arch, (lo, hi) in expect.items():
        n = count_params(get_config(arch))
        assert lo <= n <= hi, f"{arch}: {n:.3e} outside [{lo:.1e}, {hi:.1e}]"


def test_shape_applicability():
    assert supports_shape(get_config("recurrentgemma-9b"), get_shape("long_500k"))
    assert supports_shape(get_config("xlstm-125m"), get_shape("long_500k"))
    assert not supports_shape(get_config("qwen2.5-32b"), get_shape("long_500k"))
    assert supports_shape(get_config("qwen2.5-32b"), get_shape("decode_32k"))
