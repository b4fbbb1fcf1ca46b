"""The plain float32 reference against the program at a tiny size, on the CPU.

Tolerances, each with its reason:
* program in float32 against the reference: 2e-4 absolute on logits of
  order 1 — the same arithmetic in another order of summation (XLA's CPU
  dots and the program's masked, cached attention) moves float32 results by
  some ulps (~1e-7 relative) per op over two layers; 2e-4 still fails any
  wrong position, rotation, grouping or scale, which move logits by O(0.1);
* the program in bfloat16 picks the reference's best token, or one within
  0.05 of it: bf16 rounds every layer output to 2^-8 relative, which moves
  logits of order 1 by ~1e-2 at two layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_cpu import BENCH, TINY  # noqa: F401  (puts the benchmark on the path)
from chiplib.cell import seed_key
from chiplib.registry import Registry

REG = Registry(BENCH.parents[1] / "BENCHMARK.json")
REF = REG.module("refs", "dense_gqa")
SYS = REG.module("systems", "repro_dense_gqa")


def conf(dtype: str = "float32") -> dict:
    c = REG.config("phi4-mini-3.8b")
    c["config"] = dict(c["config"], **TINY, torch_dtype=dtype)
    c["compute_dtype"] = dtype
    return c


def program(c: dict, seed: int):
    from repro.models import Model
    mc = SYS.model_config(c)
    w = REF.make_weights(c["config"], seed_key(seed), jnp.dtype(c["config"]["torch_dtype"]))
    return Model(mc), SYS.to_program(w), w


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_forward_matches_reference(seed):
    c = conf()
    model, params, w = program(c, seed)
    toks = jax.random.randint(jax.random.PRNGKey(seed % 97), (2, 40), 0, TINY["vocab_size"])
    got, _ = model.forward(params, {"tokens": toks})
    want = REF.logits(c["config"], w, toks)
    assert float(jnp.abs(got - want).max()) < 2e-4


def test_prefill_then_decode_matches_reference():
    c = conf()
    model, params, w = program(c, 5)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 30), 0, TINY["vocab_size"])
    want = REF.logits(c["config"], w, toks)[0]
    logits, cache = model.prefill(params, {"tokens": toks[:, :20]}, cache_len=64)
    assert float(jnp.abs(logits[0, -1] - want[19]).max()) < 2e-4
    for t in range(20, 30):
        logits, cache = model.decode_step(params, cache, {"tokens": toks[:, t:t + 1]})
        assert float(jnp.abs(logits[0, 0] - want[t]).max()) < 2e-4


def test_bf16_program_serves_the_reference_best():
    c = conf("bfloat16")
    model, params, w = program(c, 11)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 48), 0, TINY["vocab_size"])
    served = jnp.argmax(model.forward(params, {"tokens": toks})[0], -1)
    gaps = REF.served_gaps(c["config"], w, toks, served)
    assert float(gaps.max()) < 0.05


def test_weights_fill_the_program_layout():
    for name in ("phi4-mini-3.8b", "phi4-mini-3.8b-pp8"):
        cf = REG.config(name)
        c = cf["config"]
        shapes = jax.eval_shape(lambda k: REF.make_weights(c, k, jnp.dtype(c["torch_dtype"])),
                                seed_key(0))
        SYS.check_layout(SYS.model_config(cf), shapes)


def test_partial_rope_leaves_the_last_quarter_unrotated():
    d = REF.dims(dict(REG.config("phi4-mini-3.8b")["config"]))
    assert d["rot"] == 96 and d["Dh"] == 128
    x = jnp.ones((1, 3, 2, 128))
    y = REF._rope(x, jnp.arange(3)[None], d)
    np.testing.assert_array_equal(np.asarray(y[..., 96:]), 1.0)
    assert float(jnp.abs(y[0, 2, :, :96] - 1.0).max()) > 0.1


def test_reference_train_step_matches_the_program_in_float32():
    """One AdamW step of the program's train step against the reference's
    loss, gradient and update, both in float32."""
    c = conf()
    mix = {"batch": 2, "seq": 16, "remat": "none",
           "optimizer": {"name": "adamw", "peak_lr": 3e-4, "warmup": 0, "total": 10000,
                         "final_frac": 0.1, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                         "weight_decay": 0.1}}
    mc = SYS.model_config(c)
    step, opt = SYS.train_step(mc, mix)
    w = REF.make_weights(c["config"], seed_key(4), jnp.float32)
    params = SYS.to_program(w)
    state = {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    x = jax.random.randint(jax.random.PRNGKey(3), (2, 17), 0, TINY["vocab_size"])
    loss, g = REF.loss_and_grad(c["config"], w, x[:, :-1], x[:, 1:])
    z = jax.tree.map(jnp.zeros_like, w)
    w1, _, _ = REF.adamw(mix["optimizer"], w, z, z, g, 1.0)
    state, met = step(state, {"tokens": x[:, :-1], "labels": x[:, 1:]})   # donates w
    assert abs(float(met["loss"]) - float(loss)) < 1e-5 * float(loss)
    got = SYS.from_program(state["params"])
    for k in w:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(w1[k]), atol=1e-6, rtol=1e-5)
