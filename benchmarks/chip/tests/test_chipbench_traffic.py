"""The traffic generator: deterministic per seed, the stated distributions
and rounding, and the same work for every seed."""
from __future__ import annotations

import json
import math

import numpy as np
import pytest

from chipbench_cpu import BENCH
from chiplib import traffic

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
REASON = json.loads((BENCH / "traffic" / "reason.json").read_text())
SEEDS = [0, 1, 2**31 + 11, 3 * 2**32 + 5]


@pytest.mark.parametrize("spec", [CHAT["prompt"], CHAT["output"], REASON["prompt"],
                                  REASON["output"]])
def test_lengths_follow_the_spec(spec):
    qs = traffic.quantile_lengths(spec, 400)
    r = spec.get("round_up", 1)
    assert all(spec["min"] <= q <= -(-spec["max"] // r) * r and q % r == 0 for q in qs)
    assert qs == sorted(qs)
    assert set(qs) <= set(traffic.buckets(spec))
    mid = qs[len(qs) // 2]
    if spec["dist"] == "lognormal":
        assert spec["median"] <= mid <= spec["median"] + r    # median, rounded up
        lo = spec["median"] * math.exp(-spec["sigma"])        # 15.9 % quantile
        assert abs(qs[int(0.159 * 400)] - lo) <= r + 1
    else:
        assert abs(mid - (spec["min"] + spec["max"]) / 2) <= r


def test_chat_prompts_have_seven_shapes():
    assert traffic.buckets(CHAT["prompt"]) == [128, 256, 384, 512, 640, 768, 896]
    assert traffic.buckets(REASON["prompt"]) == [64, 128, 192, 256]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_inputs_and_every_seed_the_same_work(seed):
    def draw(s):
        due = traffic.poisson_arrivals(4.8, 30, traffic.rng(s, 1))
        lens = traffic.lengths(CHAT["prompt"], len(due), traffic.rng(s, 2))
        return due, lens, traffic.prompts(lens[:3], 200064, traffic.rng(s, 4))

    a, b, other = draw(seed), draw(seed), draw(seed + 1)
    assert a == b
    assert a[0] != other[0] and a[2] != other[2]
    assert sorted(a[1]) == sorted(other[1])
    assert np.allclose(sorted(np.diff(a[0])), sorted(np.diff(a[0])))


def test_poisson_arrivals_fill_the_window():
    due = traffic.poisson_arrivals(4.8, 30, traffic.rng(7, 1))
    assert len(due) == 144 and due[0] == 0.0
    assert all(b > a for a, b in zip(due, due[1:])) and due[-1] < 30
    gaps = np.diff(due)
    assert 0.15 < float(np.mean(gaps)) < 0.25           # 1 / 4.8 s
    assert 0.8 < float(np.std(gaps) / np.mean(gaps)) < 1.2   # exponential: cv 1
    again = traffic.poisson_arrivals(4.8, 30, traffic.rng(8, 1))
    assert sorted(np.round(np.diff(again), 9)) != [] and len(again) == len(due)
