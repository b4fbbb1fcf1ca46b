"""The expert layer's router and share, and DeepSeek-V3's YaRN and stack,
against plain numpy versions of their published formulas."""
from __future__ import annotations

import math
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_tiny_config
from repro.models import count_params, layers as L
from repro.models.params import block_cycle, lead_blocks

REPO = Path(__file__).resolve().parents[1]


def np_noaux_tc(logits, bias, G, topk_group, K, norm, scale):
    """DeepSeek-V3's gate (``topk_method`` noaux_tc), one token at a time."""
    scores = 1.0 / (1.0 + np.exp(-logits))
    T, E = scores.shape
    ids, ws = [], []
    for t in range(T):
        choice = scores[t] + bias
        group_score = np.sort(choice.reshape(G, E // G), axis=-1)[:, -2:].sum(-1)
        keep = np.argsort(-group_score, kind="stable")[:topk_group]
        masked = np.full(E, -np.inf)
        for g in keep:
            masked[g * (E // G):(g + 1) * (E // G)] = choice[g * (E // G):(g + 1) * (E // G)]
        top = np.argsort(-masked, kind="stable")[:K]
        w = scores[t, top]
        ids.append(top)
        ws.append((w / w.sum() if norm else w) * scale)
    return np.array(ids), np.array(ws)


def np_softmax_topk(logits, K):
    """The softmax router: top-k of the softmax, renormalised."""
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ids = np.argsort(-p, axis=-1, kind="stable")[:, :K]
    w = np.take_along_axis(p, ids, -1)
    return ids, w / w.sum(-1, keepdims=True)


def _by_id(ids, w):
    return [dict(zip(i.tolist(), x.tolist())) for i, x in zip(ids, w)]


@pytest.mark.parametrize("E,G,topk_group,K", [(8, 4, 2, 2), (64, 8, 4, 8)])
def test_noaux_tc_router_matches_numpy(E, G, topk_group, K):
    cfg = get_config("deepseek-v3-671b").replace(
        d_model=32, num_experts=E, n_group=G, topk_group=topk_group, top_k=K)
    rng = np.random.default_rng(E)
    x = rng.standard_normal((64, 32)).astype(np.float32)
    router = {"w": (rng.standard_normal((32, E)) / math.sqrt(32)).astype(np.float32),
              "bias": (0.1 * rng.standard_normal(E)).astype(np.float32)}
    ids, w, aux = L._route(cfg, jnp.asarray(x), jax.tree.map(jnp.asarray, router))
    logits = np.asarray(jnp.einsum("td,de->te", x, router["w"],
                                   precision=jax.lax.Precision.HIGHEST))
    want_ids, want_w = np_noaux_tc(logits, router["bias"], G, topk_group, K, True, 2.5)
    got, want = _by_id(np.asarray(ids), np.asarray(w)), _by_id(want_ids, want_w)
    assert [set(g) for g in got] == [set(h) for h in want]
    for g, h in zip(got, want):
        np.testing.assert_allclose([g[k] for k in h], list(h.values()), rtol=1e-5)
    assert float(aux) == 0.0
    # group masking decides: the best K over all experts differ somewhere
    ungrouped = np.argsort(-(1 / (1 + np.exp(-logits)) + router["bias"]), axis=-1)[:, :K]
    assert any(set(a) != set(b) for a, b in zip(ungrouped, want_ids))
    # the bias picks but does not weigh: the weights are the plain scores
    assert all(abs(sum(h.values()) - 2.5) < 1e-5 for h in want)


def test_softmax_router_is_unchanged():
    """olmoe's router: top-k of the softmax, renormalised, the Switch aux
    loss on the first choice, no scaling."""
    cfg = get_tiny_config("olmoe-1b-7b")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((40, cfg.d_model)).astype(np.float32)
    wr = (rng.standard_normal((cfg.d_model, cfg.num_experts)) / 8).astype(np.float32)
    ids, w, aux = L._route(cfg, jnp.asarray(x), {"w": jnp.asarray(wr)})
    logits = np.asarray(jnp.einsum("td,de->te", x, wr, precision=jax.lax.Precision.HIGHEST))
    want_ids, want_w = np_softmax_topk(logits, cfg.top_k)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    first = np.eye(cfg.num_experts)[want_ids[:, 0]]
    want_aux = cfg.num_experts * np.mean(first.mean(0) * p.mean(0)) * cfg.router_aux_coef
    assert float(aux) == pytest.approx(want_aux, rel=1e-5)


def test_held_experts_compute_only_their_routes():
    """A share that holds none of a token's experts adds only the shared
    expert for it; a share of every expert is the whole layer."""
    cfg = get_tiny_config("deepseek-v3-671b").replace(dtype="float32", param_dtype="float32")
    from repro.models import init_params
    p = jax.tree.map(lambda a: a[0], init_params(cfg, jax.random.PRNGKey(0))["blocks"]["cycle"][0]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.d_model))
    ids, _, _ = L._route(cfg, x.reshape(16, -1), p["router"])
    shared = L.ffn(cfg, p["shared"], x)
    whole, _ = L.moe_ffn(cfg, p, x)
    parts = shared
    for off in range(0, cfg.num_experts, 2):
        c = cfg.replace(num_experts_held=2, expert_offset=off)
        part = dict(p, experts={k: v[off:off + 2] for k, v in p["experts"].items()})
        out, _ = L.moe_ffn(c, part, x)
        untouched = ~np.isin(np.asarray(ids), [off, off + 1]).any(-1)
        np.testing.assert_allclose(np.asarray(out[0, untouched]), np.asarray(shared[0, untouched]),
                                   atol=1e-6)
        parts = parts + (out - shared)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=1e-5)
    with pytest.raises(ValueError, match="outside the router"):
        cfg.replace(num_experts_held=2, expert_offset=7)


def np_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale, mscale_all_dim):
    """``DeepseekV3YarnRotaryEmbedding``: inverse frequencies, the factor on
    cos and sin, and the softmax scale's factor."""
    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inv = extra / factor * (1 - mask) + extra * mask

    def ms(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    return inv, ms(mscale) / ms(mscale_all_dim), ms(mscale_all_dim) ** 2


def test_yarn_matches_the_published_formula():
    cfg = get_config("deepseek-v3-671b")
    inv, mag, scale = np_yarn(64, 10000.0, 40.0, 4096, 32, 1, 1.0, 1.0)
    np.testing.assert_allclose(np.asarray(L._yarn_freqs(cfg, 64)), inv, rtol=1e-6)
    assert mag == 1.0
    assert L.attn_scale(cfg) == pytest.approx(scale) == pytest.approx(1.8739, abs=1e-4)
    # the ramp: pairs 0-9 keep their frequency, 23 on are divided by 40
    extra = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], extra[:10], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-12)
    # apply_rope rotates by those frequencies, as float32 holds them (one
    # ulp of a frequency moves the angle at position 8191 by ~5e-4)
    pos = jnp.array([[0, 5, 1000, 8191]])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 64))
    ang = np.asarray(pos, np.float32)[0, :, None] * np.asarray(L._yarn_freqs(cfg, 64))
    x1, x2 = np.asarray(x)[0, :, :, :32], np.asarray(x)[0, :, :, 32:]
    c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want = np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)
    np.testing.assert_allclose(np.asarray(L.apply_rope(cfg, x, pos))[0], want, atol=2e-5)
    assert L.attn_scale(cfg.replace(yarn_mscale_all_dim=0.0)) == 1.0


def test_deepseek_v3_stack_and_size():
    """The published stack: 3 dense MLA layers at 18432, then 58 MoE layers;
    671 B parameters without the MTP layer."""
    cfg = get_config("deepseek-v3-671b")
    assert lead_blocks(cfg) == ("mla_ffn",) * 3
    assert block_cycle(cfg) == (("mla_moe",), 58, ())
    assert count_params(cfg) == pytest.approx(671e9, rel=0.01)
    phi4 = get_config("phi4-mini-3.8b")
    assert lead_blocks(phi4) == () and block_cycle(phi4)[1] == phi4.num_layers


def test_mla_prefill_takes_the_flash_kernel_on_one_tpu(monkeypatch):
    """On one TPU the prefill's latent attention asks for the flash kernel;
    the forward that training differentiates keeps the XLA path.  The
    backend is described as a TPU and the attention recorded, then run on
    the CPU's path."""
    from repro.models import Model
    from repro.models.model import MLA_KV_BLOCK, MLA_Q_BLOCK
    cfg = get_tiny_config("deepseek-v3-671b")
    asked, real = [], L.attention

    def record(*args, **kw):
        asked.append((kw.get("strategy", "auto"), kw.get("q_block"), kw.get("kv_block")))
        return real(*args, **dict(kw, strategy="auto"))

    monkeypatch.setattr(L, "attention", record)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jnp.zeros((1, 8), jnp.int32)
    m.prefill(params, {"tokens": toks}, cache_len=16)
    assert asked and set(asked) == {("pallas", MLA_Q_BLOCK, MLA_KV_BLOCK)}
    asked.clear()
    m.forward(params, {"tokens": toks})
    assert asked and {s for s, _, _ in asked} == {"auto"}


def test_moe_share_sharded_matches_local():
    """The shard_map EP path on the held share: 4 of 8 experts from expert
    4, sharded over a 2x2 mesh's model axis, against the single-device
    layer, with the tokens sharded over that axis (16 positions: gather,
    reduce-scatter) and not (1 position: psum) (subprocess, so that the
    fake device count cannot leak)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_tiny_config
from repro.distributed.sharding import ShardingEnv, activate
from repro.models import Model, init_params
from repro.training.train_step import param_pspecs, to_named

cfg = get_tiny_config("deepseek-v3-671b").replace(
    dtype="float32", param_dtype="float32", num_experts_held=4, expert_offset=4)
m = Model(cfg)
params = init_params(cfg, jax.random.PRNGKey(0))
mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
env = ShardingEnv(mesh)
for S in (16, 1):
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, S), 0, cfg.vocab_size)
    ref, _ = m.forward(params, {"tokens": toks})
    with activate(env), mesh:
        params_s = jax.device_put(params, to_named(env, param_pspecs(cfg, env, 0)))
        toks_s = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
        out, _ = jax.jit(lambda p, t: m.forward(p, {"tokens": t}))(params_s, toks_s)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-4, (S, err)
print("SHARDED_OK", err)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"},
                       cwd=str(REPO), timeout=600)
    assert "SHARDED_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_simulator_ingests_the_lead_and_the_held_experts():
    """The simulator's ingest traces the dense lead as a block kind of its
    own and tags the held experts' grouped matmuls, a row per routed pair,
    for expert parallelism."""
    from repro.core.model_ingest import block_graphs
    cfg = get_tiny_config("deepseek-v3-671b").replace(num_experts_held=2, expert_offset=2)
    for mode, S in (("decode", 1), ("prefill", 8)):
        mg = block_graphs(cfg, 3, S, mode, cache_len=16)
        assert [(b.kind, b.repeat) for b in mg.blocks] == [("mla_ffn", 1), ("mla_moe", 2)]
        moe = mg.blocks[1].fwd
        tagged = [n for n in moe if n.attrs.get("moe_expert")]
        assert len(tagged) == 3 and all(n.out_shape[0] == 3 * S * cfg.top_k for n in tagged)


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-v3-671b"])
def test_simulator_counts_routed_pairs(arch):
    """At full width the simulator prices the experts at one row per routed
    pair, T * top_k, whatever the number of experts, and expert parallelism
    moves those rows, not the expert weights."""
    from repro.core.model_ingest import block_graphs
    from repro.core.passes.base import ParallelConfig, PassContext
    from repro.core.passes.parallelism import ExpertParallelPass
    cfg = get_config(arch)
    B, S = 2, 64
    pairs = B * S * cfg.top_k
    mg = block_graphs(cfg, B, S, "prefill")
    moe = next(b.fwd for b in mg.blocks if "moe" in b.kind)
    expert_flops = sum(n.flops for n in moe if n.attrs.get("moe_expert"))
    assert expert_flops == 6 * pairs * cfg.d_model * cfg.moe_d_ff
    ep = 8
    g = ExpertParallelPass(cfg.held_experts).apply(
        moe, PassContext(parallel=ParallelConfig(ep=ep)))
    assert sum(n.flops for n in g if n.attrs.get("moe_expert")) == expert_flops / ep
    dispatch = [n for n in g if n.kind == "all_to_all"][0]
    assert dispatch.comm_bytes == pairs * cfg.d_model * 2          # bf16 rows
