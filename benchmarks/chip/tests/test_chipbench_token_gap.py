"""``token_gap_ms``: the p90 gap between a request's successive tokens
(``Request.token_s``), over the gaps clear of the traced slice and its
read-out."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench_cpu import REPO, run_cell, tiny_tree
from chiplib import stamps
from chiplib.cell import RunData, StepRec
from chiplib.registry import Registry

reader = Registry(REPO / "BENCHMARK.json").reader("token_gap_ms.chat")


def run_data(reqs, steps, t0=100.0):
    ctx = SimpleNamespace(trace=True, workload="phi4-chat", out_dir=Path("unused"),
                          seconds=10.0, mix={"trace_s": 2.0})
    return RunData(ctx, steps, None, {"done": reqs, "t0": t0})


def test_p90_over_the_gaps_that_miss_the_slice():
    # the slice starts 4.0 s into the window and stops at 6.0 s; the loop
    # reads the trace until its next step, at 9.0 s
    steps = [StepRec(103.0, 103.2), StepRec(105.0, 105.2), StepRec(109.0, 109.2)]
    run = run_data([], steps)
    assert stamps.traced(run) == (104.0, 109.0)
    gen = np.random.default_rng(1)
    reqs, kept = [], []
    for start in np.linspace(100.0, 111.0, 23):
        token_s = list(start + np.cumsum(gen.uniform(0.01, 0.05, 12)))
        reqs.append(SimpleNamespace(arrival_s=start, token_s=token_s))
        kept += [b - a for a, b in zip(token_s, token_s[1:]) if b < 104.0 or a > 109.0]
    assert 0 < len(kept) < sum(len(r.token_s) - 1 for r in reqs)
    run.extra["done"] = reqs
    assert reader.read(run) == pytest.approx(float(np.percentile(kept, 90)) * 1e3, rel=1e-12)


def test_the_slice_runs_to_the_end_where_no_step_follows_it():
    assert stamps.traced(run_data([], [StepRec(103.0, 103.2)])) == (104.0, float("inf"))


def test_nothing_to_read_without_the_engines_stamp():
    """An engine that stamps no ``token_s`` gives no reading, and no error."""
    reqs = [SimpleNamespace(arrival_s=1.0, ttft_s=0.2, tokens=[1, 2])]
    assert reader.read(run_data(reqs, [])) is None
    assert reader.read(run_data([], [])) is None


def test_a_traced_chat_run_reports_it(tmp_path):
    tmp, root = tmp_path, tiny_tree(tmp_path)
    res, line = run_cell(tmp, root, "phi4-chat", seconds=2.0, trace=True)
    value = line["metrics"]["token_gap_ms.chat"]["value"]
    done = res.run.extra["done"]
    gaps = [b - a for r in done for a, b in zip(r.token_s, r.token_s[1:])]
    assert min(gaps) * 1e3 <= value <= max(gaps) * 1e3
    assert all(len(r.token_s) == len(r.tokens) for r in done)
