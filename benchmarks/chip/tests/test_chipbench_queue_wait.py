"""``queue_wait_ms``: the p90 wait from a request's due time to the start of
its admission, over the requests whose wait does not overlap the traced
slice."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench_cpu import REPO, run_cell, tiny_tree
from chiplib.cell import RunData, StepRec
from chiplib.registry import Registry

reader = Registry(REPO / "BENCHMARK.json").reader("queue_wait_ms.chat")


def run_data(reqs, steps, t0=100.0):
    ctx = SimpleNamespace(trace=True, workload="phi4-chat", out_dir=Path("unused"),
                          seconds=10.0, mix={"trace_s": 2.0})
    return RunData(ctx, steps, None, {"done": reqs, "t0": t0})


def test_p90_over_the_requests_whose_wait_misses_the_slice():
    # the slice starts 4.0 s into the window and stops at 6.0 s; the loop
    # reads the trace until its next step, at 9.0 s
    steps = [StepRec(103.0, 103.2), StepRec(105.0, 105.2), StepRec(109.0, 109.2)]
    gen = np.random.default_rng(0)
    reqs, kept = [], []
    for start in np.linspace(100.0, 112.0, 241):
        wait = float(gen.uniform(0.0, 0.5))
        reqs.append(SimpleNamespace(arrival_s=start - wait, start_s=start))
        if start < 104.0 or start - wait > 109.0:
            kept.append(wait)
    assert 0 < len(kept) < len(reqs)
    assert reader.read(run_data(reqs, steps)) == pytest.approx(
        float(np.percentile(kept, 90)) * 1e3, rel=1e-12)


def test_nothing_to_read_without_the_engines_stamp():
    """An engine that stamps no ``start_s`` gives no reading, and no error."""
    reqs = [SimpleNamespace(arrival_s=1.0, ttft_s=0.2)]
    assert reader.read(run_data(reqs, [])) is None
    assert reader.read(run_data([], [])) is None


def test_a_traced_chat_run_reports_it(tmp_path):
    tmp, root = tmp_path, tiny_tree(tmp_path)
    res, line = run_cell(tmp, root, "phi4-chat", seconds=2.0, trace=True)
    value = line["metrics"]["queue_wait_ms.chat"]["value"]
    waits = [r.start_s - r.arrival_s for r in res.run.extra["done"]]
    assert 0.0 <= value <= max(waits) * 1e3
    assert all(r.arrival_s <= r.start_s <= r.token_s[0] for r in res.run.extra["done"])
