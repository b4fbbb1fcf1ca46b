"""Find the knee of an open-loop serving cell: the highest rate the system
sustains without a growing queue.  Run once when a cell is defined; the
cell's mix then states a fixed rate (about 0.8 x the knee).

    python benchmarks/chip/knee.py --workload <name> --rates 2,3,4 --seconds 20

For each rate, one run of the cell's driver at that rate in one process.
A rate is sustained when the queue drains soon after the last arrival and
the last quarter of the requests waits no longer than the first: printed
as ``drain_s`` (seconds from the window's end to the last request's end)
and ``ttft_late_early`` (median TTFT of the last quarter over the first).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from chiplib import device
    from chiplib.cell import Ctx
    from chiplib.registry import Registry
    reg = Registry(BENCH.parents[1] / "BENCHMARK.json")
    try:
        devices, peaks = device.require_chips(reg.workload(args.workload)["chips"])
    except device.NoChip as e:
        sys.exit(f"knee.py: {e}")
    device.enable_compile_cache()
    for rate in [float(r) for r in args.rates.split(",")]:
        ctx = Ctx(reg, args.workload, args.seed, args.seconds, False, devices, peaks,
                  time.perf_counter())
        ctx.mix = dict(ctx.mix, rate_per_s=rate)
        res = reg.driver(ctx.mix).run(ctx)
        done = sorted(res.run.extra["done"], key=lambda r: r.arrival_s)
        t0 = res.run.extra["t0"]
        ttft = np.array([r.ttft_s for r in done])
        q = max(1, len(done) // 4)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(done), **res.e2e,
            "drain_s": max(r.finished_s for r in done) - (t0 + args.seconds),
            "ttft_late_early": float(np.median(ttft[-q:]) / np.median(ttft[:q])),
            "correct": all(v <= lim for v, lim in res.checks.values())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
