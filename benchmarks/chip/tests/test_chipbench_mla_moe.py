"""The DeepSeek-V3 configuration's plain float32 reference against the
program at a tiny size, on the CPU, and a whole tiny ``dsv3-reason`` run.

Tolerances, each with its reason:
* program in float32 against the reference: 2e-4 absolute on logits of
  order 1, as for the dense reference (the same arithmetic in another
  order of summation, ~1e-7 relative per op); a wrong rotation, YaRN ramp,
  softmax scale, router choice, weight or held expert moves logits by
  O(0.1);
* the shares' sum against the uncut layer: 1e-5 on outputs of order 1,
  float32 sums of the same terms in another order;
* the program in bfloat16 picks the reference's best token or one within
  0.05 of it, as for the dense reference.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_cpu import BENCH, run_cell, tiny_tree
from chiplib.cell import seed_key
from chiplib.registry import Registry
import control
import counts_mla_moe

REG = Registry(BENCH.parents[1] / "BENCHMARK.json")
REF = REG.module("refs", "mla_moe")
SYS = REG.module("systems", "repro_mla_moe")

# Every width cut, and 4 of a 16-wide router's experts held from expert 4,
# so that routes to absent experts, groups and YaRN's ramp all occur.
TINY = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "moe_intermediate_size": 32, "n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
        "n_routed_experts": 4, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "vocab_size": 512,
        "expert_share": {"router_experts": 16, "expert_offset": 4, "chips_sharing_each_layer": 4}}
TINY_MIX = {"driver": "closed_loop_serve", "slots": 3, "cache_len": 96, "clients": 3,
            "requests_per_client": 40,
            "prompt": {"dist": "uniform", "min": 8, "max": 24, "round_up": 8},
            "output": {"dist": "uniform", "min": 8, "max": 24}, "check_tokens": 40}
# The tiny cell's limit, set as the cell's own is (PERF.md section 6): the
# bf16 program read 0.0-0.012 on seeds 3-6, the fp8 control 0.82-1.47 (CPU).
TINY_LIMIT = {"logit_gap": 0.1}


def conf(dtype: str = "float32", **over) -> dict:
    c = REG.config("deepseek-v3-ep32")
    rs = dict(c["config"]["rope_scaling"], factor=4, original_max_position_embeddings=64)
    c["config"] = dict(c["config"], **TINY, rope_scaling=rs, torch_dtype=dtype)
    c["config"].update(over)
    c["compute_dtype"] = dtype
    return c


def test_file_states_the_published_keys_as_run():
    """The file states the published ``config.json`` keys at its top level,
    beside the ``config`` group the harness reads; the two agree, and each
    key ``reduced`` names differs from its published value."""
    f = REG.config("deepseek-v3-ep32")
    c = f["config"]
    assert set(c) - set(f) == {"architectures", "expert_share"}
    assert {k: f[k] for k in c if k in f} == {k: c[k] for k in c if k in f}
    assert all(f[k] != f["published"][k] for k in f["reduced"])


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
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < 2e-4


def test_prefill_then_decode_matches_reference():
    """Prefill, then decode through the latent cache of the lead and the MoE
    layers, against the reference's full forward pass (logits)."""
    c = conf()
    model, params, w = program(c, 5)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 30), 0, TINY["vocab_size"])
    want = REF.logits(c["config"], w, toks)[0]
    logits, cache = model.prefill(params, {"tokens": toks[:, :20]}, cache_len=64)
    assert set(cache["blocks"]) == {"lead", "cycle", "tail"}
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


def test_shares_add_up_to_the_uncut_layer():
    """Each of four chips holds 4 of the 16 experts: the parts their expert
    layers give, with the shared expert counted once, add up to the
    reference's layer with every expert held."""
    from repro.models import layers as L
    whole = conf(n_routed_experts=16,
                 expert_share={"router_experts": 16, "expert_offset": 0})
    _, params, w = program(whole, 7)
    d = REF.dims(whole["config"])
    lw = {k: v[0] for k, v in w["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, TINY["hidden_size"]))
    want = REF.moe(d, lw, x)
    shared = REF.moe(d, lw, x, experts=())
    p = jax.tree.map(lambda a: a[0], params["blocks"]["cycle"][0]["moe"])
    total = shared
    for off in range(0, 16, 4):
        mc = SYS.model_config(conf(expert_share={"router_experts": 16, "expert_offset": off}))
        part = dict(p, experts={k: v[off:off + 4] for k, v in p["experts"].items()})
        out, _ = L.moe_ffn(mc, part, x)
        total = total + (out - shared)
        np.testing.assert_allclose(np.asarray(out), np.asarray(REF.moe(
            dict(d, offset=off, held=4), dict(lw, eg=lw["eg"][off:off + 4], eu=lw["eu"][off:off + 4],
                                             ed=lw["ed"][off:off + 4]), x)), atol=1e-5)
    assert float(jnp.abs(want - shared).max()) > 0.1
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)


def test_share_gap_sets_aside_router_flips_only():
    """The comparison sets aside at most FLIP_SHARE of the served positions,
    each standing alone: three flipped positions in a hundred are set
    aside, four are not, nor two side by side (a fault over a stretch of
    the row); positions with nothing served do not count."""
    targets = jnp.array([[-1] * 5 + [1] * 100])
    gaps = jnp.zeros((1, 105)).at[0, :5].set(9.0)
    assert float(REF.share_gap(gaps, targets)[0]) == 0.0
    three = gaps.at[0, jnp.array([7, 30, 60])].set(2.0)
    assert float(REF.share_gap(three, targets)[0]) == 0.0
    assert float(REF.share_gap(three.at[0, 90].set(1.5), targets)[0]) == 1.5
    assert float(REF.share_gap(gaps.at[0, 40:42].set(jnp.array([2.0, 0.7])), targets)[0]) == \
        pytest.approx(0.7)
    assert float(REF.share_gap(gaps.at[0, 5].set(2.0), targets)[0]) == 0.0


def test_weights_fill_the_program_layout():
    cf = REG.config("deepseek-v3-ep32")
    c = cf["config"]
    shapes = jax.eval_shape(lambda k: REF.make_weights(c, k, jnp.dtype(c["torch_dtype"])),
                            seed_key(0))
    mc = SYS.model_config(cf)
    SYS.check_layout(mc, shapes)
    assert (mc.num_experts, mc.held_experts, mc.first_k_dense, mc.d_ff) == (256, 8, 1, 18432)
    back = SYS.from_program(SYS.to_program(shapes))
    assert jax.tree.structure(back) == jax.tree.structure(shapes)


@pytest.mark.parametrize("key,value", [("scoring_func", "softmax"), ("topk_method", "greedy"),
                                       ("num_nextn_predict_layers", 1),
                                       ("hidden_act", "gelu"), ("norm_topk_prob", False)])
def test_model_config_refuses_another_layer(key, value):
    with pytest.raises(ValueError, match="another layer"):
        SYS.model_config(conf(**{key: value}))


def test_counts_match_the_program_at_published_widths():
    """``counts_mla_moe.params`` counts what the program holds of the share;
    the expected chosen experts and routed pairs follow their formulas."""
    from repro.models import count_params
    cf = REG.config("deepseek-v3-ep32")
    c = cf["config"]
    assert counts_mla_moe.params(c) == count_params(SYS.model_config(cf))
    assert counts_mla_moe.attn_params(c) == pytest.approx(187.1e6, rel=1e-3)
    assert counts_mla_moe.latent_bytes_per_token(c) == 7 * 576 * 2
    f1, b1 = counts_mla_moe.routed(c, 1, "bfloat16")
    assert f1 == pytest.approx(6 * (8 * 8 / 256) * 6 * 7168 * 2048)    # 6 layers, K*8/256 pairs
    assert b1 == pytest.approx(6 * 8 * (8 / 256) * 3 * 7168 * 2048 * 2)
    _, b_many = counts_mla_moe.routed(c, 10_000, "bfloat16")
    assert b_many == pytest.approx(6 * 8 * 3 * 7168 * 2048 * 2)        # every held expert


def test_ragged_dot_ops_count_as_the_experts():
    """XLA's TPU expansion of the held experts' ragged dots into its grouped
    matmul kernel names the ops it makes ``ragged-dot-*``, with no scope;
    ``chiplib/moe_scopes`` puts each back under ``moe/moe_experts`` and
    leaves every other op as it was.  The share's decode step at 2 layers
    and a quarter of the widths compiled for a described v5e, with the
    persistent cache off (an entry for a described chip cannot be read
    back); at the tiny widths the compiler takes a dense expansion
    instead, which the cell's widths never do."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from chiplib import moe_scopes, scopes
    from repro.models import Model, abstract_cache
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    c = REG.config("deepseek-v3-ep32")
    c["config"] = dict(c["config"], num_hidden_layers=2, vocab_size=512, hidden_size=256,
                       intermediate_size=256, moe_intermediate_size=128)
    mc = SYS.model_config(c)
    w = jax.eval_shape(lambda k: REF.make_weights(c["config"], k, jnp.bfloat16), seed_key(0))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = jax.jit(Model(mc).decode_step).lower(
            on(SYS.to_program(w)), on(abstract_cache(mc, 8, 96)),
            {"tokens": jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one)}).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    raw = scopes.op_names(hlo)
    ragged = {k for k, v in raw.items() if v.startswith("ragged-dot")}
    assert ragged and all(scopes.scope_of(v) == "other" for k, v in raw.items() if k in ragged)
    names = moe_scopes.expert_names(raw)
    assert all(scopes.scope_of(names[k], ("moe_experts",)) == "moe_experts" for k in ragged)
    assert all(scopes.scope_of(names[k]) == "moe" for k in ragged)
    assert {k: v for k, v in names.items() if k not in ragged} == \
        {k: v for k, v in raw.items() if k not in ragged}


# ---------------------------------------------------------------- whole runs

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("dsv3")
    root = tiny_tree(tmp)
    (root / "configs" / "deepseek-v3-ep32.json").write_text(json.dumps(conf("bfloat16")))
    (root / "traffic" / "reason-8k.json").write_text(json.dumps(TINY_MIX))
    (root / "limits" / "dsv3-reason.json").write_text(json.dumps(TINY_LIMIT))
    return tmp, root


def test_sound_dsv3_run_is_correct_and_the_control_is_not(tree):
    res, line = run_cell(*tree, "dsv3-reason", seconds=4.0, trace=True)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) <= {"mla_moe_decode_mfu.dsv3", "moe_share.dsv3"}
    ctl = control.control_readings(res.run.ctx, res)["control"]
    assert ctl["logit_gap"] > line["checks"]["logit_gap"]["limit"]


def test_router_without_its_bias_is_not_correct(tree, monkeypatch):
    """A router fault: the program drops the correction bias from its
    choice, which moves more positions than the comparison sets aside."""
    from repro.models import layers as L
    real = L._route

    def route(cfg, xf, router):
        return real(cfg, xf, dict(router, bias=jnp.zeros_like(router["bias"])))

    monkeypatch.setattr(L, "_route", route)
    _, line = run_cell(*tree, "dsv3-reason", seconds=3.0)
    gap = line["checks"]["logit_gap"]
    assert not line["correct"] and gap["limit"] < gap["value"] < float("inf")


def test_altered_token_is_not_correct(tree, monkeypatch):
    """Every fourth served token altered: more than the share of positions
    the comparison sets aside."""
    from repro.serving import ServingEngine
    real = ServingEngine.step

    def step(self):
        n = real(self)
        for r in list(self.active.values()) + self.finished[-2:]:
            if len(r.tokens) % 4 == 2:
                r.tokens[-1] = (r.tokens[-1] + self.cfg.vocab_size // 2) % self.cfg.vocab_size
        return n

    monkeypatch.setattr(ServingEngine, "step", step)
    _, line = run_cell(*tree, "dsv3-reason", seconds=3.0)
    gap = line["checks"]["logit_gap"]
    assert not line["correct"] and gap["limit"] < gap["value"] < float("inf")
