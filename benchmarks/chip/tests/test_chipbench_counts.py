"""counts.py against hand-computed phi4-mini numbers."""
from __future__ import annotations

import json

import pytest

from chipbench_cpu import BENCH
import counts

PHI4 = json.loads((BENCH / "configs" / "phi4-mini-3.8b.json").read_text())["config"]
PP8 = json.loads((BENCH / "configs" / "phi4-mini-3.8b-pp8.json").read_text())["config"]
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def test_layer_and_model_sizes():
    # q,k,v: 3072 x (24 + 8 + 8) x 128; o: 24 x 128 x 3072; MLP: 3 x 3072 x 8192
    assert counts.layer_matmul_params(PHI4) == 15_728_640 + 9_437_184 + 75_497_472
    assert counts.layer_matmul_params(PHI4) == 100_663_296
    n = 32 * (100_663_296 + 2 * 3072) + 200_064 * 3072 + 3072
    assert counts.params(PHI4) == n == 3_836_021_760
    assert counts.weight_bytes(PHI4, "bfloat16") == 7_672_043_520


def test_kv_bytes_per_token():
    assert counts.kv_bytes_per_token(PHI4) == 2 * 32 * 8 * 128 * 2 == 131_072


def test_decode_step():
    flops, nbytes = counts.decode_step(PHI4, [100, 300], "bfloat16")
    per_tok = 2 * (32 * 100_663_296 + 3072 * 200_064)
    attn = 4 * 32 * 24 * 128 * 400
    assert flops == 2 * per_tok + attn
    assert nbytes == 7_672_043_520 + 131_072 * (400 + 2)
    t, bound = counts.least_time_s(flops, nbytes, PEAK)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_prefill_counts_the_causal_half():
    flops, nbytes = counts.prefill(PHI4, 512, "bfloat16")
    assert flops == (512 * 2 * 32 * 100_663_296 + 2 * 3072 * 200_064
                     + 4 * 32 * 24 * 128 * 512 * 513 / 2)
    assert nbytes == 7_672_043_520 + 131_072 * 512
    assert counts.least_time_s(flops, nbytes, PEAK)[1] == "compute"


def test_train_flops_per_token_of_the_pp8_share():
    matmul = 4 * 100_663_296 + 3072 * 25_008
    attn = 3 * 4 * 4 * 24 * 128 * 2049 / 2
    assert counts.train_flops_per_token(PP8, 2048) == 6 * matmul + attn
    assert 3.02e9 < counts.train_flops_per_token(PP8, 2048) < 3.04e9
