"""Simulator invariants: engines, overlap models, memory liveness,
collective formulas, scheduler, explorer pruning/Pareto."""
import math

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import (
    Cluster, DecodeWorkload, SimSpec, SweepSpace, TrainWorkload,
    spec_replace, sweep,
)
from repro.configs import get_config
from repro.core import ParallelConfig, Simulator
from repro.core.backend.analytical import AnalyticalEngine
from repro.core.backend.collectives import (
    GroupSpec, collective_time_us, hierarchical_collective_time_us,
    link_traffic_bytes,
)
from repro.core.backend.hardware import TPU_V5E
from repro.core.backend.prediction import RandomForest
from repro.core.ir import Graph, OpNode
from repro.core.memory import graph_liveness_peak
from repro.core.overlap import apply_ratio_overlap, bandwidth_aware_comm
from repro.core.scheduler import Interval, Timeline, schedule


# ---------------- collectives ----------------

def test_collective_byte_formulas():
    n, b = 8, 1024.0
    assert link_traffic_bytes("all_reduce", b, n) == pytest.approx(2 * 7 / 8 * b)
    assert link_traffic_bytes("all_gather", b, n) == pytest.approx(7 / 8 * b)
    assert link_traffic_bytes("reduce_scatter", b, n) == pytest.approx(7 / 8 * b)
    assert link_traffic_bytes("all_to_all", b, n) == pytest.approx(7 / 8 * b)


@settings(max_examples=25, deadline=None)
@given(payload=st.floats(1e3, 1e9), n=st.integers(2, 64))
def test_collective_time_monotone_in_payload(payload, n):
    t1 = collective_time_us("all_reduce", payload, n, TPU_V5E.intra)
    t2 = collective_time_us("all_reduce", payload * 2, n, TPU_V5E.intra)
    assert t2 >= t1


def test_hierarchical_crosspod_slower_than_intra():
    b = 64e6
    intra = hierarchical_collective_time_us("all_reduce", b, GroupSpec(16, 1), TPU_V5E)
    cross = hierarchical_collective_time_us("all_reduce", b, GroupSpec(16, 2), TPU_V5E)
    assert cross > intra


# ---------------- analytical engine ----------------

def test_roofline_compute_vs_memory_bound():
    eng = AnalyticalEngine(TPU_V5E)
    compute_heavy = OpNode("a", "matmul", flops=1e12, bytes_in=1e6, bytes_out=1e6,
                           attrs={"mm_dims": (1024, 1024, 1024)})
    mem_heavy = OpNode("b", "elementwise", flops=1e6, bytes_in=1e9, bytes_out=1e9)
    t_c = eng.latency_us(compute_heavy)
    t_m = eng.latency_us(mem_heavy)
    assert t_c == pytest.approx(1e12 / (TPU_V5E.peak_flops["bf16"] * 0.85) * 1e6 + 0.3, rel=0.05)
    assert t_m == pytest.approx(2e9 / (TPU_V5E.hbm_bw * 0.8) * 1e6 + 0.3, rel=0.05)


def test_mxu_misalignment_penalty():
    eng = AnalyticalEngine(TPU_V5E)
    aligned = OpNode("a", "matmul", flops=1e12, attrs={"mm_dims": (1024, 1024, 1024)})
    skinny = OpNode("b", "matmul", flops=1e12, attrs={"mm_dims": (1024, 5, 1024)})
    assert eng.latency_us(skinny) > eng.latency_us(aligned)


# ---------------- scheduler + overlap ----------------

def _tl(specs):
    return Timeline(intervals=[Interval(f"i{k}", kind, stream, s, e,
                                        comm_bytes=cb)
                               for k, (kind, stream, s, e, cb) in enumerate(specs)])


def test_ratio_overlap_only_extends():
    tl = _tl([("matmul", "compute", 0, 100, 0),
              ("all_reduce", "dp_comm", 0, 80, 1e6)])
    before = [i.dur for i in tl.intervals]
    out = apply_ratio_overlap(tl, TPU_V5E)
    for iv, b in zip(out.intervals, before):
        assert iv.dur >= b


def test_no_overlap_no_change():
    tl = _tl([("matmul", "compute", 0, 100, 0),
              ("all_reduce", "dp_comm", 100, 180, 1e6)])
    out = apply_ratio_overlap(tl, TPU_V5E)
    assert out.intervals[0].dur == 100
    assert out.intervals[1].dur == 80


def test_bandwidth_aware_single_flow_unchanged():
    tl = [Interval("a", "all_gather", "c1", 0, 100, comm_bytes=1e6)]
    out = bandwidth_aware_comm(tl)
    assert out[0].end == pytest.approx(100)


def test_bandwidth_aware_two_flows_share():
    """Two identical concurrent flows each take ~2x alone-time (paper Fig 6)."""
    tl = [Interval("a", "all_gather", "c1", 0, 100, comm_bytes=1e6),
          Interval("b", "all_gather", "c2", 0, 100, comm_bytes=1e6)]
    out = bandwidth_aware_comm(tl)
    for iv in out:
        assert iv.end == pytest.approx(200, rel=1e-6)


@settings(max_examples=25, deadline=None)
@given(starts=st.lists(st.floats(0, 50), min_size=1, max_size=6),
       durs=st.lists(st.floats(1, 40), min_size=6, max_size=6))
def test_bandwidth_aware_never_faster(starts, durs):
    tl = [Interval(f"f{i}", "all_gather", f"s{i}", s, s + d, comm_bytes=d * 1e5)
          for i, (s, d) in enumerate(zip(starts, durs))]
    out = bandwidth_aware_comm(tl)
    for before, after in zip(sorted(tl, key=lambda i: i.start), out):
        assert after.end >= before.end - 1e-6


def test_scheduler_respects_deps():
    g = Graph("g")
    a = g.op("matmul", flops=1e9)
    b = g.op("matmul", deps=[a.name], flops=1e9)
    tl = schedule(g, AnalyticalEngine(TPU_V5E))
    iv = {i.name: i for i in tl.intervals}
    assert iv[b.name].start >= iv[a.name].end


# ---------------- memory liveness ----------------

def test_liveness_chain_vs_fanout():
    chain = Graph("chain")
    prev = None
    for i in range(5):
        prev = chain.op("elementwise", deps=[prev.name] if prev else [],
                        bytes_out=100.0)
    peak_chain, _ = graph_liveness_peak(chain)
    assert peak_chain == pytest.approx(200.0)  # producer + consumer live

    fan = Graph("fan")
    root = fan.op("elementwise", bytes_out=100.0)
    mids = [fan.op("elementwise", deps=[root.name], bytes_out=100.0) for _ in range(4)]
    fan.op("elementwise", deps=[m.name for m in mids], bytes_out=100.0)
    peak_fan, _ = graph_liveness_peak(fan)
    assert peak_fan > peak_chain  # all four mids alive together


@settings(max_examples=20, deadline=None)
@given(sizes=st.lists(st.floats(1, 1e6), min_size=1, max_size=20))
def test_liveness_peak_bounds(sizes):
    g = Graph("g")
    prev = None
    for s in sizes:
        prev = g.op("elementwise", deps=[prev.name] if prev else [], bytes_out=s)
    peak, _ = graph_liveness_peak(g)
    assert peak >= max(sizes) - 1e-9
    assert peak <= sum(sizes) + 1e-9


# ---------------- random forest ----------------

def test_random_forest_fits_smooth_function():
    rng = np.random.default_rng(0)
    X = rng.uniform(0, 10, (400, 3))
    y = X[:, 0] * 2 + np.sin(X[:, 1]) + 0.1 * X[:, 2]
    rf = RandomForest(n_trees=12, max_depth=8).fit(X[:300], y[:300])
    pred = rf.predict(X[300:])
    mae = np.mean(np.abs(pred - y[300:]))
    assert mae < 0.8


# ---------------- simulator end-to-end sanity ----------------

def test_simulator_sane_mfu_and_scaling():
    sim = Simulator("tpu_v5e", engine="analytical")
    cfg = get_config("gemma-7b")
    par = ParallelConfig(tp=16, dp=16, sp=16, zero_stage=1)
    spec = SimSpec(cfg, parallel=par,
                   workload=TrainWorkload(global_batch=256, seq_len=4096))
    r = sim.run(spec)
    assert 0.02 < r.mfu < 1.0
    assert r.memory.total > 0
    # doubling batch should not reduce tokens/s
    r2 = sim.run(spec_replace(spec, {"workload.global_batch": 512}))
    assert r2.tokens_per_s >= r.tokens_per_s * 0.95


def test_simulator_decode_batch_throughput_monotone():
    sim = Simulator("tpu_v5e", engine="analytical")
    cfg = get_config("gemma-7b")
    par = ParallelConfig(tp=16, dp=16)
    spec = SimSpec(cfg, parallel=par,
                   workload=DecodeWorkload(global_batch=16, seq_len=8192))
    t8 = sim.run(spec)
    t64 = sim.run(spec_replace(spec, {"workload.global_batch": 64}))
    assert t64.tps_per_chip > t8.tps_per_chip  # weights amortise over batch


def test_explorer_pruning_and_pareto():
    sim = Simulator("tpu_v5e", engine="analytical")
    cfg = get_config("xlstm-125m")
    base = SimSpec(cfg, cluster=Cluster("tpu_v5e", chips=16),
                   workload=DecodeWorkload(seq_len=2048))
    res = sweep(SweepSpace(base, {"tp": (1, 2, 4), "pp": (1,),
                                  "batch": (8, 16, 100)}), sim=sim)
    assert res.pruned, "divisibility rule should prune batch=100 w/ dp"
    front = res.pareto()
    xs = [1e6 / r.report.step_time_us for r in front]
    ys = [r.tps_per_chip for r in front]
    assert xs == sorted(xs, reverse=True) or len(front) == 1
    best = res.best_under_slo(tpot_ms=1e9)
    assert best is not None
    assert best.tps_per_chip == max(r.tps_per_chip for r in res.evaluated)


# ---------------- analysis passes ----------------

def test_analysis_pipeline_flops_pre_post_recompute():
    from repro.core.passes.analysis import AnalysisPipeline, FlopsAnalysis, mfu
    from repro.core.passes.base import PassContext
    from repro.core.passes.recompute import RecomputePass
    g = Graph("g")
    a = g.op("matmul", flops=1e9, bytes_in=1e6, bytes_out=1e6, phase="fwd")
    g.op("matmul", deps=[a.name], flops=1e9, bytes_in=1e6, bytes_out=1e6, phase="bwd")
    pipe = AnalysisPipeline(post_passes=[RecomputePass("block")])
    res = pipe.run(g, PassContext(parallel=ParallelConfig()))
    assert res["model_flops"] == pytest.approx(2e9)
    assert res["executed_flops"] == pytest.approx(3e9)  # fwd recomputed in bwd
    assert res["recompute_overhead"] == pytest.approx(0.5)
    assert 0 < mfu(1e12, 1e6, 1, 197e12) < 1


def test_device_kind_table_maps_known_chips_only():
    from repro.core.backend.hardware import hardware_for_device_kind
    assert hardware_for_device_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="no HardwareSpec"):
        hardware_for_device_kind("TPU v9 imaginary")
