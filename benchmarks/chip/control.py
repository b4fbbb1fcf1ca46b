"""Readings that set a cell's limits: the program's numbers over many seeds,
and the control's and the faults' numbers, at the cell's own size.

    python benchmarks/chip/control.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] --seconds <s> [--out <file.jsonl>]

One process runs the cell's driver once per seed, with a short window at
the cell's own load, and prints the numbers its check compares.  For the
control seeds it also reads:

* serving — the fp8 control: the reference with every matrix product's
  operands rounded to float8 e4m3, read on the same sampled prompts and
  served tokens: the widest gap below the reference's best logit of the
  token the control puts first;
* training — the fp8 control's three steps against the reference's, and
  the fault of half the batch left out (the mean over the rest), by the
  same three numbers.  A state left unchanged reads 1 by the measure and
  needs no run.

The benchmark's own runs never run this.  Without a TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def control_readings(ctx, res) -> dict:
    """The control's (and, for training, the faults') numbers for one seed."""
    from chiplib import serving, training
    if "sample" in res.run.extra:
        picked = res.run.extra["sample"]
        gaps = serving.widest_gaps(ctx, picked, ctx.mix["cache_len"], control=True)
        return {"control": {"logit_gap": max(gaps)}}
    ref = training.reference_steps(ctx)
    out = {}
    for name, kw in (("control", {"quantize": "fp8"}),
                     ("half_batch", {"rows": ctx.mix["batch"] // 2})):
        read = training.reference_steps(ctx, **kw)
        out[name] = {k: v for k, (v, _) in training.compare(read, ref).items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from chiplib import device
    from chiplib.cell import Ctx
    from chiplib.registry import Registry
    reg = Registry(BENCH.parents[1] / "BENCHMARK.json")
    try:
        devices, peaks = device.require_chips(reg.workload(args.workload)["chips"])
    except device.NoChip as e:
        sys.exit(f"control.py: {e}")
    device.enable_compile_cache()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    try:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            ctx = Ctx(reg, args.workload, seed, args.seconds, False, devices, peaks, t0)
            res = reg.driver(ctx.mix).run(ctx)
            rec = {"workload": args.workload, "seed": seed, "seconds": args.seconds,
                   "program": {k: v for k, (v, _) in res.checks.items()},
                   "e2e": res.e2e, "attempted": res.attempted, "failed": res.failed}
            if seed in controls:
                rec.update(control_readings(ctx, res))
            rec["wall_s"] = time.perf_counter() - t0
            print(json.dumps(rec), flush=True)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
