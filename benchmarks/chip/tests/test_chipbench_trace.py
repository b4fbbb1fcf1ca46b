"""The trace reduction on a small recorded trace of the chip
(``data/trace_slice.json.gz``: a slice of a serving run of phi4-mini on one
TPU v5e, as ``chiplib.tracing.load`` reads it), against plain recounts."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench_cpu import BENCH  # noqa: F401  (puts the benchmark on the path)
from chiplib import tracing

DATA = Path(__file__).parent / "data" / "trace_slice.json.gz"


@pytest.fixture(scope="module")
def events():
    return json.loads(gzip.decompress(DATA.read_bytes()))


@pytest.fixture(scope="module")
def tr(events):
    return tracing.Trace.from_events(events)


def test_the_slice_holds_a_tpu_and_the_harness_spans(tr):
    assert tr.devices() == ["/device:TPU:0"]
    assert {s[2] for s in tr.spans} >= {"admit", "decode"}
    assert tr.window_s > 0


def test_idle_share_against_a_grid_count(tr):
    """Busy time by the union of intervals against a count on a 1 us grid."""
    grid = np.arange(tr.lo, tr.hi, 1000.0)
    busy = np.zeros(len(grid), bool)
    for s, e, _ in tr.ops["/device:TPU:0"]:
        busy[np.searchsorted(grid, s):np.searchsorted(grid, e)] = True
    assert tr.busy_s() == pytest.approx(busy.mean() * tr.window_s, rel=1e-2)
    assert tr.idle_share() == pytest.approx(1 - busy.mean(), abs=1e-2)
    assert 0 < tr.idle_share() < 1


def test_step_device_time(tr):
    """Every run of the decode program lies inside a harness step span, its
    ops fill it, and their self times add up to its length."""
    runs = tr.module_runs("decode_step")
    assert runs
    ops = tr.ops["/device:TPU:0"]
    for s, e, dev in runs:
        span = tr.span_of(s)
        assert span is not None and span[2] in ("admit", "decode") and span[3] is not None
        assert 0.9 * (e - s) * 1e-9 < tr.op_time_in(s, e) <= (e - s) * 1e-9 * 1.001
        mine = [r for r in ops if s <= r[0] < e]
        assert sum(t for _, _, t in tr.self_times(mine)) == pytest.approx(
            tr.op_time_in(s, e), rel=1e-6)


def test_ops_outside_the_decode_program(tr):
    """Ops in an admit span, leaving out the decode program, are the
    admission's own (prefill, scatter): positive, and less than the span."""
    decode = [(s, e) for s, e, _ in tr.module_runs("decode_step")]
    admits = [(max(a, tr.lo), min(b, tr.hi)) for a, b, name, _ in tr.spans
              if name == "admit" and a < tr.hi and b > tr.lo]
    assert admits
    for a, b in admits:
        own = tr.op_time_in(a, b, exclude=decode)
        assert 0 < own < (b - a) * 1e-9
        assert own <= tr.op_time_in(a, b)


def test_breakdown(tr):
    ops = tr.top_ops(10)
    assert 0 < len(ops) <= 10 and all(ops[i][1] >= ops[i + 1][1] for i in range(len(ops) - 1))
    gaps = tr.idle_gaps(10)
    assert 0 < len(gaps) <= 10 and all(g[1] > 0 for g in gaps)
    assert sum(g for _, g in tr.idle_gaps(10 ** 6)) == pytest.approx(
        tr.window_s - tr.busy_s(), rel=1e-6)


def test_compile_counter_counts_each_program_once():
    """JAX reports every program once as a backend compile, and a load from
    the persistent cache also as a retrieval after the time it saved."""
    from chiplib.device import CompileCounter
    cc = CompileCounter()
    cc._event(CompileCounter.PROGRAM, 2.0)          # before the window: ignored
    cc.on = True
    cc._event(CompileCounter.PROGRAM, 0.9)           # a compile
    for saved, got in ((1.5, 0.1), (0.2, 0.05)):     # two loads
        cc._event(CompileCounter.SAVED, saved)
        cc._event(CompileCounter.LOADED, got)
        cc._event(CompileCounter.PROGRAM, got)
    assert cc.programs == 3
    assert cc.load_compile_s == pytest.approx([1.6, 0.25])
    assert cc.summary().startswith("3 programs inside the window: 2 loaded from "
                                   "the compile cache, 1 compiled")
    assert "1 of them under 1 s" in cc.summary()


def test_occupancy_is_a_time_average_with_idle_time_empty():
    from chiplib.cell import StepRec
    from chiplib.serving import occupancy
    steps = [StepRec(0.0, 1.0, kv_lens=[10, 30]), StepRec(1.0, 2.0, kv_lens=[20]),
             StepRec(3.0, 4.0, kv_lens=[])]
    text = occupancy(steps, 0.0, slots=4, cache_len=100)
    # live: (2 + 1 + 0) / 4 s; rows: (40 + 20) / 4 s over 4 x 100 rows
    assert text == ("0.75 of 4 slots live on average (at most 2), holding "
                    "3.75 % of the KV cache's rows")
