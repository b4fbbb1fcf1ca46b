"""Every per-layer reader, and the trace reduction they stand on, reads the
same numbers on the recorded slice (``data/trace_slice.json.gz``) as when
the benchmark was accepted: the values below were read then.  A change to
the reduction that moves one of them changes what an accepted metric
means, and needs a benchmark change of its own.

The harness's step records are not in the slice; the readers that need
them read the same made-up records here every time.
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench_cpu import BENCH, REPO
from chiplib import tracing
from chiplib.cell import RunData, StepRec
from chiplib.device import peaks_for
from chiplib.registry import Registry

DATA = Path(__file__).parent / "data" / "trace_slice.json.gz"

FROZEN = {
    "admit_ms": 159.6000000000002,
    "decode_step_ms": 19.299999999999873,
    "decode_mfu": 53.740238968138264,
    "prefill_mfu": None,          # the slice's one admit span outlasts it
    "train_mfu": None,            # no train step in a serving slice
    "idle_share": 62.66599400000001,
}


@pytest.fixture(scope="module")
def tr():
    return tracing.Trace.from_events(json.loads(gzip.decompress(DATA.read_bytes())))


def steps() -> list:
    """Step records for the slice's step indices 0-86: the last admits a
    512-token prompt, the fourth overlaps the profiler's start."""
    out = []
    for k in range(87):
        adm = [512] if k == 86 else []
        t0 = 0.025 * k
        out.append(StepRec(t0, t0 + 0.0185 + 0.0004 * (k % 5) + (0.16 if adm else 0.0), adm,
                           [100 + 7 * i + k for i in range(16)], 16 + len(adm), edge=k == 3))
    return out


@pytest.mark.parametrize("reader", sorted(FROZEN))
def test_reader_reads_what_it_read_when_accepted(tr, reader):
    reg = Registry(REPO / "BENCHMARK.json")
    ctx = SimpleNamespace(conf=reg.config("phi4-mini-3.8b"), mix=reg.traffic("chat"),
                          peaks=peaks_for("TPU v5 lite", BENCH / "peaks.json"), devices=[None])
    value = reg.reader(reader).read(RunData(ctx, steps(), tr, {}))
    want = FROZEN[reader]
    assert value == (None if want is None else pytest.approx(want, rel=1e-12))


def test_every_frozen_reader_is_still_read():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(FROZEN) <= {m["name"].split(".", 1)[0] for m in bench["per_layer"]}


def test_reduction_reads_what_it_read_when_accepted(tr):
    assert tr.window_s == pytest.approx(0.2500000000000001, rel=1e-12)
    assert tr.busy_s() == pytest.approx(0.09333501500000001, rel=1e-12)
    assert [(s[2], s[3]) for s in tr.spans] == [("decode", k) for k in range(80, 86)] + [
        ("admit", 86)]
    assert len(tr.module_runs("decode_step")) == 5
    assert tr.top_ops(3) == [
        ["jit_decode_step/bitcast_add_fusion.3", pytest.approx(0.010898418999999994, rel=1e-12)],
        ["jit_decode_step/fusion.148", pytest.approx(0.010863851000000004, rel=1e-12)],
        ["jit_decode_step/fusion.149", pytest.approx(0.010863161999999996, rel=1e-12)]]
    assert tr.idle_gaps(3) == [["admit", pytest.approx(0.057242699900000096, rel=1e-12)],
                               ["decode", pytest.approx(0.0020760080000000003, rel=1e-12)],
                               ["decode", pytest.approx(0.00179808, rel=1e-12)]]
