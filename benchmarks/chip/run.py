"""The on-chip benchmark: one run of one cell.

    python benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, its driver, its limits and
its per-layer metric readers are all found by name from ``BENCHMARK.json``
(see ``chiplib/registry.py``).  The run refuses to start without a TPU of a
kind that ``peaks.json`` knows, or with fewer chips than the cell needs:
it then exits non-zero and prints no result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with its limit, which also close standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(ctx, res) -> dict:
    """The run's result line from a driver's :class:`Result`."""
    from chiplib import device
    reg, name = ctx.reg, ctx.workload
    correct = all(math.isfinite(v) and v <= lim for v, lim in res.checks.values())
    dev = dict(device.describe(ctx.devices), memory_peak_bytes=res.memory_peak_bytes)
    line = {"correct": correct, "attempted": res.attempted, "failed": res.failed}
    if ctx.trace:
        tr = res.run.trace
        if tr is None:
            raise RuntimeError("a traced run whose window held no traced slice")
        metrics = {}
        for m in reg.metrics("per_layer", name):
            value = reg.reader(m["name"]).read(res.run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        line["metrics"] = metrics
        line["device"] = dev
        line["breakdown"] = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
    else:
        line["metrics"] = {m["name"]: {"value": res.e2e[m["name"]], "unit": m["unit"]}
                           for m in reg.metrics("end_to_end", name)}
        line["device"] = dev
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in res.checks.items()}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    from chiplib import device
    from chiplib.cell import Ctx
    from chiplib.registry import Registry
    reg = Registry(BENCH.parents[1] / "BENCHMARK.json")
    cell = reg.workload(args.workload)
    try:
        devices, peaks = device.require_chips(cell["chips"])
    except device.NoChip as e:
        sys.exit(f"run.py: {e}")
    device.enable_compile_cache()
    ctx = Ctx(reg, args.workload, args.seed, args.seconds, bool(args.trace), devices,
              peaks, T_START)
    res = reg.driver(ctx.mix).run(ctx)
    line = result_line(ctx, res)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
