"""``attribute.py``'s engine-span reductions and ``chiplib/scopes.py``'s scope
reduction on small recorded slices of the chip, against plain recounts.

``data/engine_slice.json.gz`` holds cuts of traced runs of the cells on one
TPU v5e: ``reason`` (0.1 s of ``phi4-reason``: decode steps), ``chat`` (one
admission of ``phi4-chat`` and the decode step after it) and ``train``
(one step of ``phi4-train-pp8``).  Each holds the harness's events as
``chiplib.tracing.load`` reads them, with the window span set to the cut,
the engine's spans (``[name, start_ns, dur_ns, args]``), and ``names``: the
``op_name`` of each instruction of the program that ran, from its compiled
HLO text.
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench_cpu import BENCH  # noqa: F401  (puts the benchmark on the path)
import attribute
from chiplib import scopes, tracing

DATA = Path(__file__).parent / "data" / "engine_slice.json.gz"
SLICES = ("reason", "chat")


@pytest.fixture(scope="module")
def slices():
    return json.loads(gzip.decompress(DATA.read_bytes()))


def attribution(sl) -> attribute.Attribution:
    return attribute.Attribution(tracing.Trace.from_events(sl["events"]), sl["engine"])


def idle_grid(att):
    """The slice on a 1 us grid, and where the device is idle on it."""
    tr = att.tr
    grid = np.arange(tr.lo, tr.hi, 1000.0)
    busy = np.zeros(len(grid), bool)
    for s, e, _ in tr.ops[att.dev]:
        busy[np.searchsorted(grid, s):np.searchsorted(grid, e)] = True
    return grid, ~busy


def test_the_slices_hold_the_engine_spans(slices):
    chat, reason = attribution(slices["chat"]), attribution(slices["reason"])
    assert {sp[2] for sp in chat.engine} == {"engine.admit", "engine.prefill",
                                             "engine.first_token", "engine.scatter",
                                             "engine.decode", "engine.sample"}
    assert {sp[2] for sp in reason.engine} >= {"engine.decode", "engine.sample"}
    admit = next(sp for sp in chat.engine if sp[2] == "engine.admit")
    assert set(admit[3]) == {"rid", "slot", "prompt_len", "queued"}
    # the harness's own reduction sees only its own spans
    for att in (chat, reason):
        assert {sp[2] for sp in att.tr.spans} <= set(tracing.HARNESS_SPANS)


@pytest.mark.parametrize("name", SLICES)
def test_idle_inside_each_span_against_a_grid_count(slices, name):
    att = attribution(slices[name])
    grid, idle = idle_grid(att)
    for a, b, span, _ in att.engine:
        inside = (grid >= a) & (grid < b)
        assert att.idle_in(a, b) == pytest.approx(idle[inside].sum() * 1e-6, abs=2e-5), span


@pytest.mark.parametrize("name", SLICES)
def test_span_table_and_idle_shares_against_plain_recounts(slices, name):
    att = attribution(slices[name])
    grid, idle = idle_grid(att)
    spans = att.spans()
    for span, row in spans.items():
        mine = [(a, b) for a, b, n, _ in att.engine
                if n == span and a >= att.tr.lo and b <= att.tr.hi]
        assert row["count"] == len(mine)
        assert row["median_ms"] == pytest.approx(np.median([b - a for a, b in mine]) * 1e-6)
    shares = att.idle()
    in_any = np.zeros(len(grid), bool)
    for a, b, *_ in att.engine:
        in_any |= (grid >= a) & (grid < b)
    assert shares["idle_s"] == pytest.approx(idle.sum() * 1e-6, rel=1e-2)
    assert shares["in_engine"] == pytest.approx((idle & in_any).sum() / idle.sum(), abs=1e-2)
    assert shares["in_engine"] > 0.9


def test_admit_idle_lies_inside_engine_admit(slices):
    att = attribution(slices["chat"])
    assert att.idle()["admit_in_engine_admit"] > 0.9
    row = att.spans()["engine.prefill"]
    assert row["idle_median_ms"] > 0.5 * att.spans()["engine.admit"]["idle_median_ms"]


@pytest.mark.parametrize("name", SLICES)
def test_idle_gap_labels(slices, name):
    """Each gap is named by the harness span and then the shortest engine
    span that hold its middle."""
    att = attribution(slices[name])
    gaps = att.idle_gaps(10 ** 6)
    assert sorted(g for _, g in gaps) == pytest.approx(
        sorted(g for _, g in att.tr.idle_gaps(10 ** 6)))
    want = []
    for a, b in att.gaps():
        mid = (a + b) / 2
        harness = [sp for sp in att.tr.spans if sp[0] <= mid < sp[1]]
        engine = [sp for sp in att.engine if sp[0] <= mid < sp[1]]
        label = min(harness, key=lambda sp: sp[1] - sp[0])[2] if harness else "host"
        if engine:
            label += "/" + min(engine, key=lambda sp: sp[1] - sp[0])[2]
        want.append([label, (b - a) * 1e-9])
    assert sorted(map(tuple, gaps)) == sorted(map(tuple, want))
    assert any("/engine." in label for label, _ in gaps)


def test_decode_runs_lie_inside_engine_decode(slices):
    for name in SLICES:
        att = attribution(slices[name])
        runs = att.tr.module_runs("decode_step")
        holders = [(a, b) for a, b, n, _ in att.engine if n == "engine.decode"]
        held = sum(any(a <= (s + e) / 2 < b for a, b in holders) for s, e, _ in runs)
        got = att.inside()["decode_step"]
        assert got["share"] == held / len(runs) == 1.0
        # the clocks agree to within a few milliseconds
        assert all(abs(x) < 3.0 for x in got["skew_ms"])


def test_scope_shares_against_a_plain_recount(slices):
    att = attribution(slices["reason"])
    names = slices["reason"]["names"]["decode_step"]
    runs = att.tr.module_runs("decode_step")
    ops = [(s, e, n) for s, e, n in att.tr.ops[att.dev]
           if any(a <= s < b for a, b, _ in runs)]
    by_scope: dict = {}
    for _, name, secs in tracing.Trace.self_times(ops):
        scope = scopes.scope_of(names.get(scopes.instruction(name), ""))
        by_scope[scope] = by_scope.get(scope, 0.0) + secs
    tot = sum(by_scope.values())
    got = attribute.scope_shares(att.tr, slices["reason"]["names"])["decode_step"]
    assert got["self_s"] == pytest.approx(tot)
    assert got["scope"] == pytest.approx({k: v / tot for k, v in by_scope.items()})
    assert {"attn", "ffn", "head", "other"} <= set(got["scope"])
    # the few ops that carry no op_name (copies) take under 1 % of the time
    unnamed = sum(e - s for s, e, n in ops if scopes.instruction(n) not in names)
    assert unnamed * 1e-9 < 0.01 * tot


KINDS = ("attn_ffn", "moe_attn_ffn", "mla_moe")


@pytest.mark.parametrize("op_name, scope, kind", [
    ("jit(decode_step)/while/body/closed_call/attn_ffn/attn/dot_general", "attn", "attn_ffn"),
    ("jit(train_step)/transpose(jvp(while))/body/transpose(jvp(attn_ffn))/ffn/mul", "ffn",
     "attn_ffn"),
    ("jit(train_step)/jvp(head)/bsd,dv->bsv/dot_general", "head", "other"),
    ("jit(train_step)/optimizer/mul", "optimizer", "other"),
    ("jit(decode_step)/while/body/dynamic_slice", "other", "other"),
    ("", "other", "other"),
])
def test_scope_of(op_name, scope, kind):
    assert scopes.scope_of(op_name) == scope
    assert scopes.scope_of(op_name, KINDS) == kind


def test_op_names_from_hlo_text():
    text = """HloModule jit_decode_step
  %fusion.148 = bf16[8]{0} fusion(%a), calls=%f.5, metadata={op_name="x/attn_ffn/ffn/dot" id=1}
  ROOT %tuple.3 = (f32[8]) tuple(%x), metadata={op_name="jit(decode_step)/head/convert"}
  %param.1 = f32[8] parameter(0)
"""
    assert scopes.op_names(text) == {
        "fusion.148": "x/attn_ffn/ffn/dot",
        "tuple.3": "jit(decode_step)/head/convert"}
    assert scopes.instruction("%fusion.148 = bf16[8,8192]{1,0} fusion(...)") == "fusion.148"


@pytest.mark.parametrize("cell, program, want", [
    ("phi4-reason", "decode_step", {"attn", "ffn", "head", "embed"}),
    ("phi4-train-pp8", "train_step", {"attn", "ffn", "head", "embed", "optimizer"}),
])
def test_program_hlo_names_the_cells_ops(tmp_path, cell, program, want):
    """The cell's program compiled again after a run, here at tiny sizes on
    the CPU: its instructions carry the scopes."""
    import time

    import jax

    from chipbench_cpu import tiny_tree
    from chiplib.cell import Ctx
    from chiplib.device import peaks_for
    from chiplib.registry import Registry
    root = tiny_tree(tmp_path)
    ctx = Ctx(Registry(tmp_path / "BENCHMARK.json", root), cell, 3, 1.0, True,
              jax.devices()[:1], peaks_for("TPU v5 lite", root / "peaks.json"),
              time.perf_counter(), out_dir=tmp_path / "out")
    names = scopes.program_names(ctx, program)
    assert {scopes.scope_of(n) for n in names.values()} >= want | {"other"}
    assert any(n.startswith(f"jit({program})/") for n in names.values())
