"""The serving engine's spans and per-request stamps, and the model's and the
train step's named scopes.

The spans are ``jax.profiler.TraceAnnotation``s on the profiler's host
timeline; here a tiny engine runs under ``jax.profiler.trace`` and the trace
is read back from its ``.xplane.pb``.  The scopes are ``jax.named_scope``s:
they name the ops' ``op_name`` in the compiled program.
"""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_tiny_config
from repro.configs.base import RunConfig, ShapeConfig
from repro.models import Model, zero_cache
from repro.models.params import block_cycle, lead_blocks
from repro.serving import Request, ServingEngine
from repro.training.optimizer import adamw, cosine_schedule
from repro.training.train_step import make_train_step

PROMPTS = [[1, 2, 3, 4], [9, 8, 7], [5, 5, 5, 5, 5]]   # 3 requests, 2 slots
MAX_NEW = [4, 3, 5]
CHILDREN = ["engine.prefill", "engine.first_token", "engine.scatter"]


def serve(tmp_path=None):
    """Serve PROMPTS on a tiny engine, under the profiler when ``tmp_path``
    is given.  Returns (finished requests by rid, engine.* host events as
    (name, start_ns, end_ns, stats) sorted by start)."""
    cfg = get_tiny_config("phi4-mini-3.8b")
    params = Model(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=2, cache_len=32)

    def run():
        for i, (p, n) in enumerate(zip(PROMPTS, MAX_NEW)):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=n))
        return {r.rid: r for r in eng.run_until_drained(max_steps=100)}

    if tmp_path is None:
        return run(), []
    with jax.profiler.trace(str(tmp_path)):
        done = run()
    return done, engine_events(tmp_path)


def engine_events(tmp_path):
    """The engine.* host events of the trace written under ``tmp_path``, as
    (name, start_ns, end_ns, stats) sorted by start."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if not plane.name.startswith("/device")
              for line in plane.lines for e in line.events if e.name.startswith("engine.")]
    return sorted(events, key=lambda ev: ev[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return serve(tmp_path_factory.mktemp("trace"))


def inside(outer, events):
    """The events that lie within ``outer``'s interval, in start order."""
    return [ev for ev in events if ev is not outer and outer[1] <= ev[1] and ev[2] <= outer[2]]


def test_the_six_spans_are_on_the_timeline(traced):
    done, events = traced
    assert {ev[0] for ev in events} == {"engine.admit", *CHILDREN, "engine.decode",
                                        "engine.sample"}
    admits = [ev for ev in events if ev[0] == "engine.admit"]
    assert [a[3]["rid"] for a in admits] == [0, 1, 2]
    assert [a[3]["prompt_len"] for a in admits] == [len(p) for p in PROMPTS]
    assert [a[3]["queued"] for a in admits] == [2, 1, 0]
    assert sorted(a[3]["slot"] for a in admits[:2]) == [0, 1]
    for a in admits:
        kids = inside(a, events)
        assert [k[0] for k in kids] == CHILDREN
        assert all(k[3]["rid"] == a[3]["rid"] for k in kids)
        assert all(kids[i][2] <= kids[i + 1][1] for i in range(len(kids) - 1))
    decodes = [ev for ev in events if ev[0] == "engine.decode"]
    assert decodes and all(1 <= d[3]["active"] <= 2 for d in decodes)
    for d in decodes:
        assert [k[0] for k in inside(d, events)] == ["engine.sample"]
    # no span of the engine is nested in a decode, and none overlaps another
    # at the same level
    tops = [ev for ev in events if ev[0] in ("engine.admit", "engine.decode")]
    assert all(tops[i][2] <= tops[i + 1][1] for i in range(len(tops) - 1))
    # one decode step per served token after the first, of the longest request
    assert len(decodes) >= max(MAX_NEW) - 1


def test_sample_reads_every_live_slot_at_once(traced):
    """Each ``engine.sample`` sits in its ``engine.decode`` and carries the
    number of live slots it read: their sum is every token served after the
    first ones, which the admissions read."""
    done, events = traced
    samples = []
    for d in (ev for ev in events if ev[0] == "engine.decode"):
        (s,) = inside(d, events)
        assert s[0] == "engine.sample" and s[3]["tokens"] == d[3]["active"]
        samples.append(s[3]["tokens"])
    assert 2 in samples
    assert sum(samples) == sum(len(r.tokens) - 1 for r in done.values())


def test_one_host_read_per_decode_step(monkeypatch):
    """A decode step reads its tokens in one ``jax.device_get``, with 1, 2
    or 3 slots live; ``host_reads`` grows by one per step and one per
    admission (its first token)."""
    gets = []
    device_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: gets.append(x) or device_get(x))
    cfg = get_tiny_config("phi4-mini-3.8b")
    params = Model(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=3, cache_len=32)
    # one admission in each of the first three steps, then two plain steps
    for step, live in enumerate([1, 2, 3, 3, 3]):
        if step < len(PROMPTS):
            eng.submit(Request(rid=step, prompt=PROMPTS[step], max_new_tokens=8))
        reads, before = eng.host_reads, len(gets)
        assert eng.step() == live
        assert eng.host_reads - reads == (step < len(PROMPTS)) + 1
        assert len(gets) - before == 1 and gets[-1].shape == (3,)
    assert eng.host_reads == len(PROMPTS) + 5
    assert [len(r.tokens) for r in eng.active.values()] == [6, 5, 4]


def test_request_stamps(traced):
    done, _ = traced
    assert sorted(done) == [0, 1, 2]
    for rid, r in done.items():
        assert len(r.tokens) == len(r.token_s) == MAX_NEW[rid]
        assert r.arrival_s <= r.start_s <= r.arrival_s + r.ttft_s
        assert r.arrival_s + r.ttft_s == pytest.approx(r.token_s[0], abs=1e-9)
        assert r.ttft_s == r.token_s[0] - r.arrival_s
        assert all(a < b for a, b in zip(r.token_s, r.token_s[1:]))
        assert r.finished_s == r.token_s[-1]
    # the third request waits for a free slot: it starts after one finished
    assert done[2].start_s >= min(done[0].finished_s, done[1].finished_s)


def test_served_tokens_do_not_depend_on_the_profiler(traced):
    done, _ = traced
    plain, _ = serve()
    assert {k: r.tokens for k, r in plain.items()} == {k: r.tokens for k, r in done.items()}


def test_prefill_compiles_once_per_prompt_length(tmp_path):
    """Two prompts of one length, then one of another, each admitted in a
    step of its own: the second admission of a seen length compiles no
    program, and each ``engine.prefill`` span says whether its admission
    met a new length."""
    cfg = get_tiny_config("phi4-mini-3.8b")
    params = Model(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=3, cache_len=32)
    compiled = []

    def listen(name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiled.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    per_step = []
    try:
        with jax.profiler.trace(str(tmp_path)):
            for rid, prompt in enumerate([[1, 2, 3, 4], [5, 6, 7, 8], [9, 8, 7]]):
                eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=4))
                before = len(compiled)
                eng.step()
                assert eng.active and max(r.rid for r in eng.active.values()) == rid
                per_step.append(len(compiled) - before)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    # the first step compiles the prefill and the decode step, the second
    # (same length, another slot) nothing, the third a prefill for length 3
    assert per_step[0] > 0 and per_step[1] == 0 and per_step[2] > 0
    prefills = [ev for ev in engine_events(tmp_path) if ev[0] == "engine.prefill"]
    assert [p[3]["rid"] for p in prefills] == [0, 1, 2]
    assert [bool(p[3]["compiled"]) for p in prefills] == [True, False, True]


def op_names(fn, *args) -> set:
    """The ``op_name`` of each op of ``fn`` compiled for ``args``: what a
    device trace shows per op."""
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', txt))


# The scopes each block kind opens inside its own.
MOE_SCOPES = ("moe", "moe/moe_route", "moe/moe_experts")
KIND_SCOPES = {"attn_ffn": ("attn", "ffn"), "moe_attn_ffn": ("attn", *MOE_SCOPES),
               "mla_moe": ("attn", *MOE_SCOPES), "mla_ffn": ("attn", "ffn"),
               "griffin_attn": ("attn", "ffn"),
               "griffin_rec": ("ffn",), "xattn": ("attn", "ffn"), "mlstm": (),
               "slstm": ()}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_scopes(arch):
    cfg = get_tiny_config(arch)
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = dict(jax.eval_shape(lambda: zero_cache(cfg, 2, 16)),
                 pos=jax.ShapeDtypeStruct((2,), jnp.int32))
    names = op_names(model.decode_step, params, cache,
                     {"tokens": jax.ShapeDtypeStruct((2, 1), jnp.int32)})
    joined = "\n".join(names)
    assert "jit(decode_step)/embed/" in joined and "jit(decode_step)/head/" in joined
    cycle, _, tail = block_cycle(cfg)
    for kind in {*lead_blocks(cfg), *cycle, *tail}:
        assert f"/{kind}/" in joined
        for scope in KIND_SCOPES[kind]:
            assert f"/{kind}/{scope}/" in joined, (kind, scope)


def test_train_step_scopes():
    cfg = get_tiny_config("phi4-mini-3.8b")
    params = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    opt = adamw(cosine_schedule(3e-4, warmup=0, total=100, final_frac=0.1))
    run = RunConfig(model=cfg, shape=ShapeConfig("t", 16, 2, "train"), remat_policy="block")
    state = {"params": params, "opt": jax.eval_shape(opt.init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32) for k in ("tokens", "labels")}
    names = op_names(make_train_step(cfg, run, opt), state, batch)
    assert any(n.startswith("jit(train_step)/optimizer/") for n in names)
    assert any("attn_ffn/attn/" in n for n in names)
    assert any("attn_ffn/ffn/" in n for n in names)
    # the optimizer's scope holds no op of the forward or backward pass
    assert not any("optimizer/" in n and ("attn" in n or "ffn" in n) for n in names)
