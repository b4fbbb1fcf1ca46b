"""Tiny cells on the CPU for the benchmark's tests: a copy of the benchmark
in a temporary directory with tiny configurations, mixes and limits, and a
run of a cell there that skips the look for a chip."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512}

TINY_MIXES = {
    "chat": {"driver": "open_loop_serve", "slots": 4, "cache_len": 64, "rate_per_s": 12.0,
             "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 8, "max": 40,
                        "round_up": 8},
             "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 3, "max": 16},
             "check_tokens": 40},
    "reason": {"driver": "closed_loop_serve", "slots": 3, "cache_len": 96, "clients": 3,
               "requests_per_client": 40,
               "prompt": {"dist": "uniform", "min": 8, "max": 24, "round_up": 8},
               "output": {"dist": "uniform", "min": 8, "max": 24}, "check_tokens": 40},
    "train-s2048": {"driver": "train_steps", "batch": 4, "seq": 32, "remat": "block",
                    "optimizer": {"name": "adamw", "peak_lr": 3e-4, "warmup": 0,
                                  "total": 10000, "final_frac": 0.1, "b1": 0.9, "b2": 0.95,
                                  "eps": 1e-8, "weight_decay": 0.1}},
}


# Limits at the tiny sizes, set as the cells' own are (PERF.md section 6),
# from CPU readings of seeds 3 and 4: chat, the program's widest logit gap
# 0.0 and the fp8 control's 0.318; training, the program's loss, gradient
# and change gaps (1.2e-4, 1.3-2.5e-3, 1.3-2.0e-3), the fp8 control's
# (2.1-3.7e-3, 3.2-4.2e-2, 6.0-6.7e-3) and half the batch left out
# (1.4-1.7e-2, 0.44-0.56, 1.7-2.6e-2).
TINY_LIMITS = {
    "phi4-chat": {"logit_gap": 0.1},
    "phi4-reason": {"logit_gap": 0.1},
    "phi4-train-pp8": {"loss_gap": 6e-4, "grad_gap": 1e-2, "change_gap": 4e-3},
}


def tiny_tree(tmp: Path, limits: dict = TINY_LIMITS) -> Path:
    """A copy of the benchmark under ``tmp`` with every configuration cut to
    tiny sizes, every mix to tiny shapes and every limit to the tiny size's.
    Returns the copy's root."""
    root = tmp / "chip"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", ".jax_cache", ".out", "__pycache__"))
    for f in (root / "configs").glob("*.json"):
        conf = json.loads(f.read_text())
        conf["config"].update(TINY)
        f.write_text(json.dumps(conf))
    for name, mix in TINY_MIXES.items():
        (root / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for name, lim in limits.items():
        (root / "limits" / f"{name}.json").write_text(json.dumps(lim))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return root


def run_cell(tmp: Path, root: Path, workload: str, *, seed: int = 3, seconds: float = 1.0,
             trace: bool = False):
    """One run of ``workload`` in the tiny tree, on the CPU: the driver's
    result and the run's result line."""
    import jax
    sys.path.insert(0, str(BENCH))
    import run as bench_run
    from chiplib.cell import Ctx
    from chiplib.device import peaks_for
    from chiplib.registry import Registry
    reg = Registry(tmp / "BENCHMARK.json", root)
    ctx = Ctx(reg, workload, seed, seconds, trace, jax.devices()[:1],
              peaks_for("TPU v5 lite", root / "peaks.json"), time.perf_counter(),
              out_dir=tmp / "out")
    res = reg.driver(ctx.mix).run(ctx)
    return res, bench_run.result_line(ctx, res)
