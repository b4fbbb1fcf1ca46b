"""Cache correctness + simulation-throughput invariants.

The memoization layers (simcache / pricing / block-stage / toposort) must be
invisible in the numbers: cached and cold ``simulate()`` produce bit-identical
``Report``s, the interval-free scheduling fast path reproduces the interval
path exactly, and repeated sweeps are deterministic."""
import pytest

from repro.api import (
    Cluster, DecodeWorkload, PrefillWorkload, SimSpec, SweepSpace,
    TrainWorkload, sweep,
)
from repro.configs import get_config
from repro.core import ParallelConfig, Simulator
from repro.core.backend.analytical import AnalyticalEngine
from repro.core.backend.hardware import TPU_V5E
from repro.core.explorer import Candidate, rule_memory_fit
from repro.core.ir import Graph
from repro.core.overlap import apply_ratio_overlap
from repro.core.scheduler import schedule, schedule_times

CFG = get_config("xlstm-125m")

SPECS = [
    SimSpec(CFG, parallel=ParallelConfig(tp=2, dp=2, pp=2, microbatches=2),
            workload=TrainWorkload(global_batch=16, seq_len=512)),
    SimSpec(CFG, parallel=ParallelConfig(tp=2, dp=2),
            workload=PrefillWorkload(global_batch=4, seq_len=512)),
    SimSpec(CFG, parallel=ParallelConfig(tp=2, dp=4),
            workload=DecodeWorkload(global_batch=8, seq_len=1024)),
]

DEC_SPEC = SPECS[2]


def _reports(sim, specs=SPECS):
    return [sim.run(s) for s in specs]


def _grid(seq_len=1024, chips=16, tp=(1, 2, 4), pp=(1, 2), batch=(8, 16, 32),
          memory_limit=0.0):
    base = SimSpec(CFG, cluster=Cluster("tpu_v5e", chips=chips,
                                        memory_limit=memory_limit),
                   workload=DecodeWorkload(seq_len=seq_len))
    return SweepSpace(base, {"tp": tp, "pp": pp, "batch": batch})


def test_cached_vs_cold_bit_identical_reports():
    cold = _reports(Simulator("tpu_v5e", engine="analytical", cache=False))
    sim = Simulator("tpu_v5e", engine="analytical", cache=True)
    warm1 = _reports(sim)
    warm2 = _reports(sim)   # second pass: everything served from cache
    assert sim.cache_stats()["block_times"]["hits"] >= 3
    assert sim.cache_stats()["memory"]["hits"] >= 3
    for c, w1, w2 in zip(cold, warm1, warm2):
        for r in (w1, w2):
            assert r.step_time_us == c.step_time_us
            assert r.breakdown_us == c.breakdown_us
            assert r.kind_us == c.kind_us
            assert r.memory.total == c.memory.total
            assert r.mfu == c.mfu


def test_fast_path_matches_interval_path():
    # keep_timelines=True forces the Interval-building path; both must agree
    sim = Simulator("tpu_v5e", engine="analytical")
    fast = sim.run(DEC_SPEC)
    slow = sim.run(DEC_SPEC, keep_timelines=True)
    assert fast.step_time_us == pytest.approx(slow.step_time_us, rel=1e-12)
    assert fast.kind_us == pytest.approx(slow.kind_us, rel=1e-12)
    assert slow.block_timelines and not fast.block_timelines


def test_schedule_times_equals_schedule_plus_overlap():
    g = Graph("g")
    a = g.op("matmul", flops=1e9, bytes_in=1e6, bytes_out=1e6)
    c = g.op("all_reduce", deps=[a.name], comm_bytes=4e6, comm_group="tp",
             comm_size=8, overlappable=True, stream="tp_comm")
    b = g.op("matmul", deps=[a.name], flops=2e9, bytes_in=1e6, bytes_out=1e6)
    g.op("elementwise", deps=[b.name, c.name], bytes_in=1e6, bytes_out=1e6,
         repeat=3)
    eng = AnalyticalEngine(TPU_V5E)
    tl = apply_ratio_overlap(schedule(g, eng), TPU_V5E)
    total, by_kind = schedule_times(g, eng, TPU_V5E)
    assert total == tl.total_time
    assert by_kind == tl.by_kind()


def test_toposort_cache_invalidation():
    g = Graph("g")
    a = g.op("matmul")
    first = g.toposort()
    assert g.toposort() is first            # cached
    b = g.op("matmul", deps=[a.name])
    order = g.toposort()
    assert order is not first and len(order) == 2
    g.remove(b.name)
    assert len(g.toposort()) == 1


def test_explore_pricing_cache_hit_rate_and_stats():
    sim = Simulator("tpu_v5e", engine="analytical")
    res = sweep(_grid(), sim=sim)
    assert res.evaluated and res.configs_per_sec > 0 and res.n_groups > 0
    pr = res.cache_stats["pricing"]
    assert pr["hits"] > 0
    assert pr["hits"] / (pr["hits"] + pr["misses"]) > 0.3
    # candidates sharing (tp, B_local) reuse whole priced block stages
    assert res.cache_stats["block_times"]["hits"] > 0
    assert res.cache_stats["ingest"]["misses"] < len(res.evaluated)


def test_explore_deterministic_pareto():
    def frontier():
        sim = Simulator("tpu_v5e", engine="analytical")
        res = sweep(_grid(), sim=sim)
        return [(r.cand.key(), r.report.step_time_us, r.tps_per_chip)
                for r in res.pareto()]
    f1, f2 = frontier(), frontier()
    assert f1 == f2

    # a warm simulator must reproduce its own cold frontier too
    sim = Simulator("tpu_v5e", engine="analytical")
    r1 = sweep(_grid(), sim=sim)
    r2 = sweep(_grid(), sim=sim)
    key = lambda res: [(r.cand.key(), r.report.step_time_us) for r in res.pareto()]
    assert key(r1) == key(r2)


def test_rule_memory_fit_prunes_before_simulation():
    rule = rule_memory_fit(1e6, mode="decode", seq_len=4096)  # 1 MB: nothing fits
    c = Candidate(ParallelConfig(tp=2, dp=8), 32)
    assert "memory-fit" in rule(CFG, c)
    roomy = rule_memory_fit(1e15, mode="decode", seq_len=4096)
    assert roomy(CFG, c) is None

    # in a sweep, infeasible candidates are pruned without being simulated
    sim = Simulator("tpu_v5e", engine="analytical")
    res = sweep(_grid(tp=(1, 2), pp=(1,), batch=(8, 16), memory_limit=1e6),
                sim=sim)
    assert not res.evaluated
    assert all(p.report is None and "memory-fit" in p.reason
               for p in res.pruned)


def test_memory_fit_estimate_is_lower_bound():
    # prune rule must never reject a candidate the simulator would accept:
    # the closed-form estimate stays below the simulated total
    sim = Simulator("tpu_v5e", engine="analytical")
    for tp, gb in [(1, 8), (2, 16), (4, 32)]:
        par = ParallelConfig(tp=tp, dp=16 // tp)
        rep = sim.run(SimSpec(CFG, parallel=par,
                              workload=DecodeWorkload(global_batch=gb,
                                                      seq_len=1024)))
        limit = rep.memory.total
        rule = rule_memory_fit(limit, mode="decode", seq_len=1024)
        assert rule(CFG, Candidate(par, gb)) is None


def test_in_memory_profile_db_reads_and_writes_no_file():
    from repro.core.backend.profiling import ProfileDB
    db = ProfileDB(None)
    assert db.path is None and not db.data
    db.put("k", 1.0, {})
    assert db.get("k") == 1.0
    with pytest.raises(ValueError):
        db.save()


def test_pricing_cache_invalidated_on_profile_db_mutation():
    # the §3.3 workflow: simulate with an empty DB (analytical fallback),
    # then add measured profiles — re-simulation must pick them up
    from repro.core.backend.profiling import ProfileDB, node_key
    from repro.core.ir import OpNode

    db = ProfileDB(path="/nonexistent/empty.json")
    sim = Simulator("tpu_v5e", engine="profiling", db=db)
    node = OpNode("mm", "matmul", flops=1e9, bytes_in=1e6, bytes_out=1e6,
                  attrs={"mm_dims": (256, 256, 256)})
    t_fallback = sim.engine.latency_us(node)
    assert sim.engine.engine_for(node) == "analytical"   # db empty
    db.put(node_key(node, sim.hw.name), 123.0, {})
    assert sim.engine.latency_us(node) == 123.0
    assert sim.engine.engine_for(node) == "profiling"
    assert t_fallback != 123.0


def test_block_stage_cache_invalidated_on_profile_db_mutation():
    from repro.core.backend.profiling import ProfileDB

    db = ProfileDB(path="/nonexistent/empty.json")
    sim = Simulator("tpu_v5e", engine="profiling", db=db)
    spec = SimSpec(CFG, parallel=ParallelConfig(tp=2, dp=4),
                   workload=DecodeWorkload(global_batch=8, seq_len=512))
    r1 = sim.run(spec)
    db.put("tpu_v5e|matmul|1,1,1|bf16", 1.0, {})   # any external put
    r2 = sim.run(spec)
    # that key matches no node, so results are equal — but they must have
    # been recomputed, not served from a stale stage (block_times missed)
    assert r2.step_time_us == r1.step_time_us
    assert sim.cache_stats()["block_times"]["misses"] >= 2


def test_collective_time_memoized_and_self_invalidating():
    from dataclasses import replace

    from repro.core.backend.collectives import (
        GroupSpec, _hierarchical_uncached, collective_memo_clear,
        collective_memo_stats, hierarchical_collective_time_us,
    )
    from repro.core.backend.hardware import TPU_V5E

    collective_memo_clear()
    args = ("all_reduce", 64e6, GroupSpec(intra_size=8, inter_size=2))
    t1 = hierarchical_collective_time_us(*args, TPU_V5E)
    assert t1 == _hierarchical_uncached(*args, TPU_V5E)   # memo is invisible
    before = collective_memo_stats().hits
    t2 = hierarchical_collective_time_us(*args, TPU_V5E)
    assert t2 == t1 and collective_memo_stats().hits == before + 1

    # the key carries the link-domain fields: different hardware (or a
    # recalibrated link) can never be served a stale entry
    slow = replace(TPU_V5E, name="slow",
                   intra=replace(TPU_V5E.intra, bandwidth=1e9))
    t_slow = hierarchical_collective_time_us(*args, slow)
    assert t_slow > t1

    collective_memo_clear()
    assert collective_memo_stats().total == 0


def test_simulate_exposes_collective_memo_stats():
    sim = Simulator("tpu_v5e", engine="analytical")
    sim.cache_clear()
    spec = SimSpec(CFG, parallel=ParallelConfig(tp=2, dp=4),
                   workload=DecodeWorkload(global_batch=8, seq_len=512))
    sim.run(spec)
    sim.run(spec)
    st = sim.cache_stats()["collectives"]
    assert st["hits"] > 0                        # repeat p2p terms memoized


def test_simulate_does_not_mutate_caller_parallel_config():
    sim = Simulator("tpu_v5e", engine="analytical")
    par = ParallelConfig(tp=2, dp=2)
    snapshot = par.key()
    sim.run(SimSpec(CFG, parallel=par,
                    workload=DecodeWorkload(global_batch=8, seq_len=512)))
    assert par.key() == snapshot
