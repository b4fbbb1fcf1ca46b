"""``attn_share`` and ``optimizer_share``: a scope's share of a program's
device self time, on slices recorded on one TPU v5e, against a recount on
a 100 ns grid, where each instant belongs to the innermost op running.

``data/engine_slice.json.gz`` (key ``reason``) holds 0.1 s of decode steps
of ``phi4-reason``; ``data/train_slice.json.gz`` one train step of
``phi4-train-pp8``: the harness's events as ``chiplib.tracing.load`` reads
them, with the window span set to the cut, and ``names``, the ``op_name``
of each instruction of the program that ran, from its compiled HLO text.
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench_cpu import REPO
from chiplib import scopes, tracing
from chiplib.cell import RunData
from chiplib.registry import Registry

DATA = Path(__file__).parent / "data"
CASES = {  # reader -> (file, key, program, scope)
    "attn_share.reason": ("engine_slice.json.gz", "reason", "decode_step", "attn"),
    "optimizer_share.train": ("train_slice.json.gz", None, "train_step", "optimizer"),
}


def recorded(reader):
    file, key, program, scope = CASES[reader]
    sl = json.loads(gzip.decompress((DATA / file).read_bytes()))
    sl = sl[key] if key else sl
    return tracing.Trace.from_events(sl["events"]), sl["names"][program], program, scope


def read(reader, tr, names, monkeypatch):
    monkeypatch.setattr(scopes, "program_names", lambda ctx, program: names)
    return Registry(REPO / "BENCHMARK.json").reader(reader).read(
        RunData(SimpleNamespace(), [], tr, {}))


def grid_share(tr, program, names, scope, step=100.0):
    """The scope's share (%) of the program's ops' time, each instant of a
    100 ns grid given to the latest-starting op that holds it."""
    num = den = 0
    for dev, ops in tr.ops.items():
        runs = [(s, e) for s, e, d in tr.module_runs(program) if d == dev]
        lo, hi = min(s for s, _ in runs), max(e for _, e in runs)
        owner = np.full(int((hi - lo) // step) + 1, -1)
        mine = [(s, e, n) for s, e, n in ops if any(a <= s < b for a, b in runs)]
        for k, (s, e, _) in enumerate(mine):
            owner[int((s - lo) // step):int((e - lo) // step)] = k
        held = owner[owner >= 0]
        under = np.array([scopes.scope_of(names.get(scopes.instruction(n), ""), (scope,)) == scope
                          for _, _, n in mine])
        num, den = num + under[held].sum(), den + len(held)
    return 100.0 * num / den


@pytest.mark.parametrize("reader", sorted(CASES))
def test_share_against_a_grid_recount(reader, monkeypatch):
    tr, names, program, scope = recorded(reader)
    value = read(reader, tr, names, monkeypatch)
    assert 0.0 < value < 100.0
    assert value == pytest.approx(grid_share(tr, program, names, scope), abs=0.3)


@pytest.mark.parametrize("reader", sorted(CASES))
def test_no_value_from_a_program_without_the_scope(reader, monkeypatch):
    """The parent of the scopes compiles the same ops with no scope in their
    op_name: no value, and no error."""
    tr, names, _, scope = recorded(reader)
    bare = {k: v.replace(f"/{scope}/", "/") for k, v in names.items()}
    assert read(reader, tr, bare, monkeypatch) is None


@pytest.mark.parametrize("reader", sorted(CASES))
def test_nothing_compiled_without_a_run_of_the_program(reader, monkeypatch):
    def refuse(ctx, program):
        raise AssertionError("compiled with nothing to read")

    monkeypatch.setattr(scopes, "program_names", refuse)
    run = Registry(REPO / "BENCHMARK.json").reader(reader).read
    assert run(RunData(SimpleNamespace(), [], None, {})) is None
    empty = tracing.Trace.from_events([["/host:CPU", "python", "window", 0.0, 1e9, {}]])
    assert run(RunData(SimpleNamespace(), [], empty, {})) is None


def test_the_recorded_train_step_is_whole():
    tr, names, program, _ = recorded("optimizer_share.train")
    runs = tr.module_runs(program)
    assert len(runs) == 1 and runs[0][1] - runs[0][0] > 0.5e9
    # the ops that carry no op_name (async copies and slices, custom calls)
    # take under 5 % of the self time
    times = tracing.Trace.self_times(tr.ops[tr.devices()[0]])
    unnamed = sum(secs for _, n, secs in times if scopes.instruction(n) not in names)
    assert unnamed < 0.05 * sum(secs for *_, secs in times)
    assert {scopes.scope_of(n) for n in names.values()} >= {"attn", "ffn", "head",
                                                           "optimizer", "other"}


@pytest.mark.parametrize("ops, want", [
    # nested: a loop and the two ops of its body, as Trace.self_times reads them
    ([(0, 10, "loop"), (2, 4, "a"), (5, 9, "b")], [4, 2, 4]),
    # an async copy the loop starts inside and outlasts: each instant once
    ([(0, 10, "outer"), (1, 4, "copy"), (3, 8, "loop"), (5, 6, "x")], [3, 2, 4, 1]),
    # two ops that start together: the shorter is the inner
    ([(0, 3, "inner"), (0, 5, "outer")], [3, 2]),
])
def test_own_times(ops, want):
    ops = [(s * 1e9, e * 1e9, n) for s, e, n in ops]
    assert scopes.own_times(ops) == pytest.approx(want)
    assert sum(scopes.own_times(ops)) == pytest.approx(
        (max(e for _, e, _ in ops) - min(s for s, _, _ in ops)) * 1e-9)
