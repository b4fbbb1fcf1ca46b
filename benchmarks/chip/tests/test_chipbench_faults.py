"""Whole runs of tiny cells on the CPU, past the look for a chip: a sound
program comes out correct, and the timed path broken underneath comes out
not correct, once for each fault a cell can have.  The control (the
reference in fp8 in the program's place) fails too.  The limits are the
tiny sizes' own (``chipbench_cpu.TINY_LIMITS``)."""
from __future__ import annotations

import json

import jax
import pytest

from chipbench_cpu import run_cell, tiny_tree
import control


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("cells")
    return tmp, tiny_tree(tmp)


def test_sound_chat_run_is_correct(tree):
    res, line = run_cell(*tree, "phi4-chat", seconds=0.5)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == 6
    assert set(line["metrics"]) == {"ttft_p90_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    ctl = control.control_readings(res.run.ctx, res)["control"]
    assert ctl["logit_gap"] > line["checks"]["logit_gap"]["limit"]


def test_sound_reason_run_is_correct_and_traced(tree):
    _, line = run_cell(*tree, "phi4-reason", seconds=6.0, trace=True)
    assert line["correct"]
    assert {"decode_step_ms.reason", "idle_share.reason"} <= set(line["metrics"])
    assert "busy_s" in line["device"] and "breakdown" in line


@pytest.mark.parametrize("cell,seconds", [("phi4-chat", 0.5), ("phi4-reason", 6.0)])
def test_altered_token_is_not_correct(tree, monkeypatch, cell, seconds):
    from repro.serving import ServingEngine
    real = ServingEngine.step

    def step(self):
        n = real(self)
        for r in list(self.active.values()) + self.finished[-2:]:
            if len(r.tokens) == 2:
                r.tokens[-1] = (r.tokens[-1] + self.cfg.vocab_size // 2) % self.cfg.vocab_size
        return n

    monkeypatch.setattr(ServingEngine, "step", step)
    _, line = run_cell(*tree, cell, seconds=seconds)
    gap = line["checks"]["logit_gap"]
    assert not line["correct"] and gap["limit"] < gap["value"] < float("inf")


def _broken_step(monkeypatch, broken):
    from repro.training import train_step as ts
    real = ts.make_train_step

    def make(cfg, run, opt):
        return broken(real(cfg, run, opt))

    monkeypatch.setattr(ts, "make_train_step", make)


def test_sound_train_run_is_correct(tree):
    res, line = run_cell(*tree, "phi4-train-pp8", seconds=0.5)
    assert line["correct"] and line["failed"] == 0
    ctx = res.run.ctx
    found = control.control_readings(ctx, res)
    assert any(found["control"][k] > ctx.limits[k] for k in ctx.limits)
    assert any(found["half_batch"][k] > ctx.limits[k] for k in ctx.limits)


def test_state_left_unchanged_is_not_correct(tree, monkeypatch):
    _broken_step(monkeypatch, lambda f: lambda st, b: (st, f(st, b)[1]))
    _, line = run_cell(*tree, "phi4-train-pp8", seconds=0.3)
    assert not line["correct"]
    assert line["checks"]["change_gap"]["value"] == 1.0


def test_half_batch_left_out_is_not_correct(tree, monkeypatch):
    def half(f):
        return lambda st, b: f(st, jax.tree.map(lambda x: x[: x.shape[0] // 2], b))

    _broken_step(monkeypatch, half)
    _, line = run_cell(*tree, "phi4-train-pp8", seconds=0.3)
    assert not line["correct"]


def test_only_numbers_with_a_limit_are_compared(tree):
    tmp, root = tree
    path = root / "limits" / "phi4-train-pp8.json"
    kept = path.read_text()
    lim = {k: v for k, v in json.loads(kept).items() if k != "loss_gap"}
    path.write_text(json.dumps(lim))
    try:
        _, line = run_cell(tmp, root, "phi4-train-pp8", seconds=0.3)
    finally:
        path.write_text(kept)
    assert line["correct"]
    assert set(line["checks"]) == set(lim)
