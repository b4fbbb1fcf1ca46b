"""End-to-end behaviour tests: serving engine, dynamic SP planner,
HLO analysis, dry-run artifact integrity, multi-device MoE equivalence."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, SHAPES, get_config, get_tiny_config, supports_shape
from repro.models import Model
from repro.serving import Request, ServingEngine, plan_batch

REPO = Path(__file__).resolve().parents[1]


# ---------------- serving engine ----------------

def test_serving_engine_continuous_batching_matches_sequential():
    cfg = get_tiny_config("gemma-7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=2, cache_len=64)
    prompts = [[1, 2, 3, 4], [9, 8, 7], [5, 5, 5, 5, 5]]  # 3 reqs, 2 slots
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    finished = eng.run_until_drained(max_steps=200)
    assert len(finished) == 3
    # sequential reference for request 0
    req = finished[[r.rid for r in finished].index(0)]
    toks = list(prompts[0])
    out = []
    logits, cache = model.prefill(params, {"tokens": jnp.asarray([toks], jnp.int32)},
                                  cache_len=64)
    tok = int(jnp.argmax(logits[0, -1]))
    out.append(tok)
    for _ in range(4):
        logits, cache = model.decode_step(params, cache,
                                          {"tokens": jnp.asarray([[tok]], jnp.int32)})
        tok = int(jnp.argmax(logits[0, 0]))
        out.append(tok)
    assert req.tokens == out


def test_serving_engine_three_slots_at_mixed_depths_match_sequential():
    """Three requests admitted two steps apart decode together at three
    depths; each one's tokens are a sequential prefill/decode_step's, and
    each is a Python int."""
    cfg = get_tiny_config("gemma-7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=3, cache_len=64)
    prompts = [[1, 2, 3, 4], [9, 8, 7], [5, 5, 5, 5, 5, 5]]
    max_new = [10, 8, 6]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=max_new[i]))
        eng.step()
        eng.step()
    assert len(eng.active) == 3
    assert len({int(p) for p in eng.cache["pos"]}) == 3
    finished = {r.rid: r for r in eng.run_until_drained(max_steps=200)}
    assert sorted(finished) == [0, 1, 2]
    for i, p in enumerate(prompts):
        toks = finished[i].tokens
        assert all(type(t) is int for t in toks)
        logits, cache = model.prefill(params, {"tokens": jnp.asarray([p], jnp.int32)},
                                      cache_len=64)
        out = [int(jnp.argmax(logits[0, -1]))]
        while len(out) < max_new[i]:
            logits, cache = model.decode_step(params, cache,
                                              {"tokens": jnp.asarray([[out[-1]]], jnp.int32)})
            out.append(int(jnp.argmax(logits[0, 0])))
        assert toks == out, i


def test_serving_engine_virtual_clock_trace_replay():
    """Caller-supplied arrival_s (including 0.0) must be honored and TTFT
    computed on the injected clock's timebase, not wall-clock."""
    from repro.serving import VirtualClock
    cfg = get_tiny_config("gemma-7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    clk = VirtualClock()
    eng = ServingEngine(cfg, params, slots=2, cache_len=64, clock=clk)
    traced = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2, arrival_s=0.0)
    eng.submit(traced)
    assert traced.arrival_s == 0.0          # was silently replaced pre-fix
    stamped = Request(rid=1, prompt=[4, 5], max_new_tokens=2)
    clk.advance_to(0.125)
    eng.submit(stamped)
    assert stamped.arrival_s == 0.125       # engine stamps via the clock
    clk.advance_to(0.25)
    finished = eng.run_until_drained(max_steps=50)
    assert len(finished) == 2
    assert finished[0].ttft_s >= 0.0
    by_rid = {r.rid: r for r in finished}
    assert by_rid[0].ttft_s == pytest.approx(0.25)   # prefill at t=0.25
    assert by_rid[1].ttft_s == pytest.approx(0.125)


def test_dynamic_sp_beats_static_zigzag():
    seq_lens = [512, 1024, 8192, 256, 16384, 768]
    static = plan_batch(seq_lens, d_head=128, n_heads=64, sp_world=8, dynamic=False)
    dynamic = plan_batch(seq_lens, d_head=128, n_heads=64, sp_world=8, dynamic=True)
    assert dynamic.makespan_us < static.makespan_us
    # short requests choose narrow SP
    short = dynamic.choices[3]
    assert short.sp <= 2


# ---------------- HLO analysis ----------------

def test_hlo_analysis_trip_counts():
    from repro.launch.hlo_analysis import analyze_module

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=7)[0]

    xa = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    wa = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = jax.jit(f).lower(xa, wa).compile().as_text()
    st = analyze_module(txt)
    assert st["flops"] == pytest.approx(7 * 2 * 64 ** 3, rel=1e-6)
    assert any(w["trip_count"] == 7 for w in st["while_loops"])


# ---------------- dry-run artifacts (deliverable e) ----------------

def test_dryrun_artifacts_complete_and_ok():
    d = REPO / "results" / "dryrun"
    if not d.exists():
        pytest.skip("dry-run sweep not executed yet")
    missing, bad = [], []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            for mesh in ("single", "multi"):
                f = d / f"{arch}__{shape}__{mesh}.json"
                if not f.exists():
                    missing.append(f.name)
                    continue
                rec = json.loads(f.read_text())
                ok = rec["status"] == "ok"
                skipped = rec["status"] == "skipped"
                expect_skip = not supports_shape(get_config(arch), SHAPES[shape])
                if expect_skip and not skipped:
                    bad.append((f.name, "should be skipped"))
                if not expect_skip and not ok:
                    bad.append((f.name, rec.get("error", rec["status"])))
    assert not missing, missing
    assert not bad, bad


def test_dryrun_records_have_roofline_inputs():
    d = REPO / "results" / "dryrun"
    if not d.exists():
        pytest.skip("dry-run sweep not executed yet")
    rec = json.loads((d / "gemma-7b__train_4k__single.json").read_text())
    assert rec["flops_per_device"] > 0
    assert rec["hbm_bytes_per_device"] > 0
    assert rec["collectives"]["traffic_bytes"] > 0
    assert rec["memory_analysis"]["temp_bytes"] > 0


# ---------------- multi-device MoE equivalence (shard_map EP path) --------

def test_moe_sharded_matches_local():
    """Run the tiny MoE under a real 4-device mesh (subprocess so the fake
    device count cannot leak into this process)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_tiny_config
from repro.distributed.sharding import ShardingEnv, activate
from repro.models import Model, init_params
from repro.training.train_step import param_pspecs, to_named

cfg = get_tiny_config("olmoe-1b-7b").replace(dtype="float32", param_dtype="float32")
m = Model(cfg)
params = init_params(cfg, jax.random.PRNGKey(0))
B, S = 4, 16
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
ref, _ = m.forward(params, {"tokens": toks})   # single-device path

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
env = ShardingEnv(mesh)
with activate(env), mesh:
    p_ns = to_named(env, param_pspecs(cfg, env, 0))
    params_s = jax.device_put(params, p_ns)
    toks_s = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
    out, _ = jax.jit(lambda p, t: m.forward(p, {"tokens": t}))(params_s, toks_s)
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-4, err
print("SHARDED_OK", err)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={"PYTHONPATH": str(REPO / "src"),
                                       "PATH": "/usr/bin:/bin"},
                       cwd=str(REPO), timeout=600)
    assert "SHARDED_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
