"""Tracer + pass invariants: flops accounting, model ingest, TP/EP rewrites,
fusion, quantization, recompute, pipeline schedules."""
import threading

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import tracer
from repro.core.ir import Graph
from repro.core.passes.base import ParallelConfig, PassContext
from repro.core.passes.fusion import FusionPass
from repro.core.passes.parallelism import ExpertParallelPass, TensorParallelPass
from repro.core.passes.pipeline import make_schedule, schedule_1f1b, schedule_gpipe
from repro.core.passes.quantize import QuantizePass
from repro.core.passes.recompute import RecomputePass


def _mlp_graph(tp_friendly=True):
    F = 512 if tp_friendly else 511

    def f(x, w1, w2):
        return jax.nn.silu(x @ w1) @ w2

    xa = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    w1 = jax.ShapeDtypeStruct((256, F), jnp.float32)
    w2 = jax.ShapeDtypeStruct((F, 256), jnp.float32)
    return tracer.trace(f, xa, w1, w2)


def test_trace_flops_exact():
    g = _mlp_graph()
    mm = g.by_kind()["matmul"]
    assert mm == 2 * 64 * 256 * 512 * 2


def test_trace_matches_xla_cost_analysis():
    def f(x, w):
        return jnp.tanh(x @ w).sum()
    xa = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    wa = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    g = tracer.trace(f, xa, wa)
    ca = jax.jit(f).lower(xa, wa).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):   # older jaxlib: one dict per device
        ca = ca[0]
    xla = ca["flops"]
    ours = g.total("flops")
    assert abs(ours - xla) / xla < 0.05


def test_scan_repeat_multiplier():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        return jax.lax.scan(body, x, None, length=9)[0]
    xa = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    wa = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    g = tracer.trace(f, xa, wa)
    assert g.total("flops") == 9 * 2 * 32 * 32 * 32


# attentions per block kind of whisper: the decoder's self and cross
# attention, the encoder's self-attention
_WHISPER_ATTENTIONS = {"xattn": 2, "enc": 1, "head": 0}


@pytest.mark.parametrize("mode", ["train", "prefill", "decode"])
def test_ingest_traces_each_attention_as_one_operator(mode):
    """``block_graphs`` hands the block functions the abstract attention:
    each attention is one ``charon_attention`` node, with its
    ``charon_attention_bwd`` (2.5x the flops) in the joint graph.
    ``layers.attention`` is never reassigned, so the model run in another
    thread while the simulator traces gives the logits it gives alone."""
    from repro.configs import get_tiny_config
    from repro.core.model_ingest import block_graphs
    from repro.models import Model, layers as L
    cfg = get_tiny_config("whisper-large-v3")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(8, dtype=jnp.int32)[None],
             "frame_embeds": jax.random.normal(jax.random.PRNGKey(1),
                                               (1, cfg.encoder_seq, cfg.d_model))}

    def forward():      # a new function each time: traced anew, calling attention
        return jax.jit(lambda p, b: model.forward(p, b)[0])(params, batch)

    alone = forward()
    real, done, traced = L.attention, threading.Event(), []

    def trace_until_done():
        while True:
            traced.append(block_graphs(cfg, 2, 16, mode, cache_len=32))
            if done.is_set():
                return

    t = threading.Thread(target=trace_until_done)
    t.start()
    try:
        during = forward()
    finally:
        done.set()
        t.join(timeout=300)
    assert not t.is_alive() and traced
    assert L.attention is real
    assert jnp.array_equal(during, alone)
    for bg in traced[-1].all_blocks():
        n = _WHISPER_ATTENTIONS[bg.kind]
        fwd = sorted(node.flops for node in bg.fwd if node.kind == "attention")
        assert len(fwd) == n, (bg.kind, fwd)
        if bg.joint is not None:
            joint = sorted(node.flops for node in bg.joint if node.kind == "attention")
            assert joint == sorted(fwd + [2.5 * f for f in fwd]), (bg.kind, joint)


def test_tp_pass_divides_and_inserts_collectives():
    g = _mlp_graph()
    base_flops = g.total("flops")
    ctx = PassContext(parallel=ParallelConfig(tp=4))
    g2 = TensorParallelPass().apply(g, ctx)
    kinds = g2.by_kind()
    assert g2.total("flops", pred=lambda n: not n.is_comm) == base_flops / 4
    assert "all_reduce" in kinds  # row-parallel second matmul


def test_tp_pass_skips_nondivisible():
    g = _mlp_graph(tp_friendly=False)
    ctx = PassContext(parallel=ParallelConfig(tp=4))
    g2 = TensorParallelPass().apply(g, ctx)
    assert "all_reduce" not in g2.by_kind()


def test_ep_pass_alltoall_pair():
    g = Graph("moe")
    a = g.op("matmul", out_shape=(8, 64, 128), flops=1e9, bytes_in=1e6, bytes_out=1e6)
    b = g.op("matmul", deps=[a.name], out_shape=(8, 64, 128), flops=1e9,
             bytes_in=1e6, bytes_out=1e6)
    c = g.op("elementwise", deps=[b.name], out_shape=(64, 128), flops=1e3,
             bytes_in=1e6, bytes_out=1e6)
    ctx = PassContext(parallel=ParallelConfig(tp=1, ep=4))
    g2 = ExpertParallelPass(num_experts=8).apply(g, ctx)
    kinds = g2.by_kind()
    a2a = [n for n in g2 if n.kind == "all_to_all"]
    assert len(a2a) == 2  # dispatch + combine
    assert g2.total("flops", pred=lambda n: n.kind == "matmul") == 2e9 / 4


def test_fusion_pass_merges_chain():
    g = Graph("f")
    a = g.op("norm", out_shape=(64, 256), flops=1e5, bytes_in=1e5, bytes_out=1e5)
    b = g.op("matmul", deps=[a.name], out_shape=(64, 512), flops=1e7,
             bytes_in=2e5, bytes_out=1e5)
    g2 = FusionPass().apply(g)
    assert len(g2) == 1
    node = next(iter(g2))
    assert node.kind == "fused" and node.flops == 1e5 + 1e7
    assert node.bytes_in == 1e5 and node.bytes_out == 1e5


def test_quantize_scales_bytes():
    g = _mlp_graph()
    before = g.total("total_bytes", pred=lambda n: n.kind == "matmul")
    g2 = QuantizePass("int8").apply(g)
    after = g2.total("total_bytes", pred=lambda n: n.kind == "matmul")
    assert after == pytest.approx(before / 4)  # f32 -> int8


def test_recompute_adds_bwd_clones():
    g = _mlp_graph()
    n_fwd = len(g)
    g2 = RecomputePass("block").apply(g)
    assert len(g2) == 2 * n_fwd
    assert sum(1 for n in g2 if n.phase == "bwd") == n_fwd


# ---------------- pipeline schedules ----------------

def test_1f1b_bubble_formula():
    p, m, tf, tb = 4, 16, 1.0, 2.0
    s = schedule_1f1b(p, m, tf, tb, 0.0)
    expect = (m * (tf + tb) + (p - 1) * (tf + tb))  # classic 1F1B makespan
    assert s.total_time == pytest.approx(expect, rel=1e-6)


def test_gpipe_worse_than_1f1b_bubble():
    for m in (4, 8, 32):
        g = schedule_gpipe(4, m, 1.0, 2.0, 0.0)
        f = schedule_1f1b(4, m, 1.0, 2.0, 0.0)
        assert f.total_time <= g.total_time + 1e-9


def test_dualpipe_beats_1f1b():
    f = make_schedule("1f1b", 8, 16, 1.0, 2.0, 0.05)
    d = make_schedule("dualpipe", 8, 16, 1.0, 2.0, 0.05)
    assert d.total_time < f.total_time
    assert d.bubble_fraction < f.bubble_fraction


@settings(max_examples=20, deadline=None)
@given(p=st.integers(1, 8), m=st.integers(1, 24),
       tf=st.floats(0.1, 5), tb=st.floats(0.1, 5))
def test_1f1b_schedule_valid(p, m, tf, tb):
    """Events never overlap per rank and respect stage dependencies."""
    s = make_schedule("1f1b" if p > 1 else "none", p, m, tf, tb, 0.0)
    ideal = m * (tf + tb)
    assert s.total_time >= ideal - 1e-9
    for r in range(p):
        evs = sorted(s.rank_events(r), key=lambda e: e.start)
        for e1, e2 in zip(evs, evs[1:]):
            assert e2.start >= e1.end - 1e-9
    fwd = {(e.rank, e.microbatch): e for e in s.events if e.kind == "F"}
    for e in s.events:
        if e.kind == "F" and e.rank > 0:
            assert e.start >= fwd[(e.rank - 1, e.microbatch)].end - 1e-9


def test_interleaved_beats_plain_1f1b():
    from repro.core.passes.pipeline import schedule_interleaved
    f = make_schedule("1f1b", 8, 16, 1.0, 2.0, 0.01)
    i = schedule_interleaved(8, 16, 1.0, 2.0, 0.01, v=2)
    assert i.bubble_fraction < f.bubble_fraction
    assert i.total_time < f.total_time
