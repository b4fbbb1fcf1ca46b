"""Attribute a traced slice of a cell to the serving engine's own spans.

    python benchmarks/chip/attribute.py --workload <name> --seed <n> --seconds <s>

One traced run of the cell, as ``run.py --trace 1`` makes it, whose trace is
also read for what the harness's reduction leaves out: the engine's host
spans (``engine.*``, with their args).  Until ``chiplib/tracing.load``
keeps them, this tool reads them from the same trace file, wrapping the
harness's loader for the run.  Prints the run's result line, then one JSON
line:

* ``spans``: per engine span name, its count in the slice, its median
  length, and the device's idle time inside it (median per span, total);
* ``idle``: the slice's idle seconds, and the shares of them inside some
  engine span and inside each span name; in a cell with admissions, the
  share of the idle time inside the harness's ``admit`` spans that lies
  inside ``engine.admit``;
* ``idle_gaps``: the longest idle gaps, each named
  ``<harness span>/<innermost engine span>`` at its middle;
* ``inside``: per program, the share of its runs in the slice whose middle
  lies inside its engine span (``decode_step`` in ``engine.decode``), and
  ``skew_ms``, the bounds that causality puts on the device clock's offset
  from the host's: a run starts after its span began, and the span ends
  after the last op it waited for;
* ``scopes``: the program's device self time by named scope
  (``chiplib/scopes.py``), as shares;
* ``steps``: per harness span name, the median length on the host, and per
  program the median device time of a run: with the profiler on.

The benchmark's own runs never run this.  Without a TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import json
import os
import sys
import time
from pathlib import Path
from statistics import median

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ENGINE = "engine."
PROGRAMS = {"decode_step": "engine.decode", "train_step": None}


def engine_spans(path: str) -> list:
    """The engine's host spans in a trace file: ``[name, start_ns, dur_ns, args]``."""
    from jax.profiler import ProfileData
    return [[e.name, float(e.start_ns), float(e.duration_ns), {k: v for k, v in e.stats}]
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device")
            for line in plane.lines for e in line.events if e.name.startswith(ENGINE)]


class Attribution:
    """A harness :class:`~chiplib.tracing.Trace` with the engine's spans
    (``[name, start_ns, dur_ns, args]``) beside it."""

    def __init__(self, tr, engine: list):
        self.tr = tr
        self.engine = sorted(((s, s + d, n, a) for n, s, d, a in engine
                              if s < tr.hi and s + d > tr.lo), key=lambda sp: sp[:3])
        self.dev = tr.devices()[0] if tr.devices() else None
        self.busy = tr.busy_intervals(self.dev) if self.dev else []
        self._ends = [b for _, b in self.busy]

    # ---- idle time
    def idle_in(self, a: float, b: float) -> float:
        """Idle seconds of the first device in [a, b), clipped to the slice."""
        a, b = max(a, self.tr.lo), min(b, self.tr.hi)
        if b <= a:
            return 0.0
        busy = 0.0
        for s, e in self.busy[bisect.bisect_right(self._ends, a):]:
            if s >= b:
                break
            busy += max(0.0, min(e, b) - max(s, a))
        return (b - a - busy) * 1e-9

    def gaps(self) -> list:
        edges = [self.tr.lo] + [x for iv in self.busy for x in iv] + [self.tr.hi]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def innermost(self, t: float):
        inner = [sp for sp in self.engine if sp[0] <= t < sp[1]]
        return min(inner, key=lambda sp: sp[1] - sp[0]) if inner else None

    def label(self, t: float) -> str:
        """``<harness span>/<innermost engine span>`` at ``t``, or the
        harness's own label where no engine span holds it."""
        sp = self.tr.span_of(t)
        outer = sp[2] if sp else "host"
        inner = self.innermost(t)
        return f"{outer}/{inner[2]}" if inner else outer

    def idle_gaps(self, n: int = 10) -> list:
        out = [[self.label((a + b) / 2), (b - a) * 1e-9] for a, b in self.gaps()]
        return sorted(out, key=lambda g: -g[1])[:n]

    def idle(self) -> dict:
        total = self.tr.window_s - self.tr.busy_s() if self.dev else 0.0
        by_name: dict = {}
        for a, b, name, _ in self.engine:
            by_name[name] = by_name.get(name, 0.0) + self.idle_in(a, b)
        tops = [sp for sp in self.engine if not any(
            o is not sp and o[0] <= sp[0] and sp[1] <= o[1] for o in self.engine)]
        out = {"idle_s": total,
               "in_engine": sum(self.idle_in(a, b) for a, b, *_ in tops) / total if total else None,
               "by_span": {k: v / total for k, v in sorted(by_name.items())} if total else {}}
        admits = [(a, b) for a, b, name, _ in self.tr.spans if name == "admit"]
        if admits:
            harness = sum(self.idle_in(a, b) for a, b in admits)
            inside = sum(self.idle_in(a, b) for a, b, name, _ in self.engine
                         if name == "engine.admit" and any(x <= a and b <= y for x, y in admits))
            out["admit_in_engine_admit"] = inside / harness if harness else None
        return out

    # ---- spans and programs
    def spans(self) -> dict:
        out: dict = {}
        for a, b, name, _ in self.engine:
            if a < self.tr.lo or b > self.tr.hi:
                continue
            out.setdefault(name, []).append(((b - a) * 1e-9, self.idle_in(a, b)))
        return {k: {"count": len(v), "median_ms": median(d for d, _ in v) * 1e3,
                    "idle_median_ms": median(i for _, i in v) * 1e3,
                    "idle_s": sum(i for _, i in v)} for k, v in sorted(out.items())}

    def inside(self) -> dict:
        out = {}
        ops = self.tr.ops.get(self.dev, [])
        for prog, span in PROGRAMS.items():
            runs = self.tr.module_runs(prog)
            holders = [(a, b) for a, b, name, _ in self.engine if name == span]
            if not runs or not holders:
                continue
            starts = sorted(a for a, _, n in self.tr.modules.get(self.dev, []) if prog in n)
            held, lo, hi = 0, -float("inf"), float("inf")
            for s, e, _ in runs:
                ab = next(((a, b) for a, b in holders if a <= (s + e) / 2 < b), None)
                if ab is None:
                    continue
                held += 1
                # the ops the span waited for: its run's and those after it,
                # up to the span's end or the program's next run
                nxt = bisect.bisect_right(starts, s)
                stop = min([ab[1]] + starts[nxt:nxt + 1])
                last = max(o[1] for o in ops[bisect.bisect_left(ops, (s,)):
                                              bisect.bisect_left(ops, (stop,))])
                lo, hi = max(lo, last - ab[1]), min(hi, s - ab[0])
            out[prog] = {"share": held / len(runs), "skew_ms": [lo * 1e-6, hi * 1e-6]}
        return out

    def steps(self) -> dict:
        tr = self.tr
        host: dict = {}
        for a, b, name, _ in tr.spans:
            if a >= tr.lo and b <= tr.hi:
                host.setdefault(name, []).append((b - a) * 1e-6)
        out = {f"{k}_host_ms": median(v) for k, v in sorted(host.items())}
        for prog in PROGRAMS:
            runs = tr.module_runs(prog)
            if runs:
                out[f"{prog}_device_ms"] = median((e - s) * 1e-6 for s, e, _ in runs)
        return out

    def report(self) -> dict:
        return {"spans": self.spans(), "idle": self.idle(), "idle_gaps": self.idle_gaps(),
                "inside": self.inside(), "steps": self.steps()}


def scope_shares(tr, names: dict) -> dict:
    """Per program (``names``: program -> instruction name -> op_name), its
    device self time in the slice and the shares of it by named scope."""
    from chiplib import scopes
    out = {}
    for prog, nm in names.items():
        by = scopes.self_time_by_scope(tr, prog, nm)
        tot = sum(by.values())
        if tot:
            out[prog] = {"self_s": tot, "scope": {k: v / tot for k, v in sorted(by.items())}}
    return out


@contextlib.contextmanager
def engine_spans_kept(got: dict):
    """For the ``with`` block, each trace file the harness loads is also read
    for the engine's spans, into ``got["engine"]``, before the harness
    deletes it."""
    from chiplib import tracing
    harness_load = tracing.load

    def load(path):
        got["engine"] = engine_spans(path)
        return harness_load(path)

    tracing.load = load
    try:
        yield got
    finally:
        tracing.load = harness_load


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run as bench_run
    from chiplib import device, scopes
    from chiplib.cell import Ctx
    from chiplib.registry import Registry
    reg = Registry(BENCH.parents[1] / "BENCHMARK.json")
    try:
        devices, peaks = device.require_chips(reg.workload(args.workload)["chips"])
    except device.NoChip as e:
        sys.exit(f"attribute.py: {e}")
    device.enable_compile_cache()
    got = {"engine": []}
    with engine_spans_kept(got):
        ctx = Ctx(reg, args.workload, args.seed, args.seconds, True, devices, peaks,
                  time.perf_counter())
        res = reg.driver(ctx.mix).run(ctx)
    print(json.dumps(bench_run.result_line(ctx, res)), flush=True)
    tr = res.run.trace
    names = {p: scopes.program_names(ctx, p) for p in PROGRAMS if tr.module_runs(p)}
    report = Attribution(tr, got["engine"]).report()
    report["scopes"] = scope_shares(tr, names)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **report}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
