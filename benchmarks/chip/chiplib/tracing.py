"""Capture a profiler trace of a slice of the window, and reduce it.

The reduction works on a plain list of events, so that it can be checked
on a small recorded trace: ``[plane, line, name, start_ns, dur_ns, meta]``
with ``meta`` a dict of the event's stats that matter here (``step`` on
the harness's spans).  Device planes are ``/device:TPU:<n>``; on each, the
``XLA Ops`` line holds the operations that ran and the ``XLA Modules`` line
the programs they belong to.  The harness's own spans
(``jax.profiler.TraceAnnotation``) are on the host plane; the one named
``window`` bounds the traced slice.
"""
from __future__ import annotations

import bisect
import glob
import shutil
from dataclasses import dataclass, field
from pathlib import Path

import jax

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
HARNESS_SPANS = ("window", "admit", "decode", "batch", "step")


def capture_start(out_dir: Path) -> None:
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(str(out_dir))


def capture_stop(out_dir: Path) -> list:
    """Stop tracing, read the trace into events and delete the files."""
    jax.profiler.stop_trace()
    files = sorted(glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True))
    try:
        return load(files[-1])
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def load(path: str) -> list:
    """The device planes' ops and modules, and the harness's host spans."""
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                name = e.name
                if not on_device and name not in HARNESS_SPANS:
                    continue
                meta = {}
                if not on_device:
                    stats = dict(e.stats)
                    if "step" in stats:
                        meta["step"] = int(stats["step"])
                events.append([plane.name, line.name, name, float(e.start_ns),
                               float(e.duration_ns), meta])
    return events


@dataclass
class Trace:
    """Events of one traced slice, by device and by kind, in trace ns."""
    lo: float
    hi: float
    ops: dict = field(default_factory=dict)       # device -> [(start, end, name)]
    modules: dict = field(default_factory=dict)   # device -> [(start, end, name)]
    spans: list = field(default_factory=list)     # [(start, end, name, step)]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @classmethod
    def from_events(cls, events: list) -> "Trace":
        win = [e for e in events if e[2] == WINDOW_SPAN and not e[0].startswith("/device")]
        if not win:
            raise ValueError("the trace holds no 'window' span")
        lo, hi = win[0][3], win[0][3] + win[0][4]
        tr = cls(lo, hi)
        for plane, line, name, start, dur, meta in events:
            iv = (start, start + dur, name)
            if plane.startswith("/device"):
                (tr.ops if line == OPS_LINE else tr.modules).setdefault(plane, []).append(iv)
            elif name != WINDOW_SPAN:
                tr.spans.append((start, start + dur, name, meta.get("step")))
        for d in (tr.ops, tr.modules):
            for v in d.values():
                v.sort()
        tr.spans.sort()
        return tr

    # ---- reductions
    def busy_intervals(self, device: str) -> list:
        """Merged intervals in which an op ran on ``device``, clipped to the
        window."""
        out: list = []
        for s, e, _ in self.ops.get(device) or self.modules.get(device, []):
            s, e = max(s, self.lo), min(e, self.hi)
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    def devices(self) -> list:
        return sorted(set(self.ops) | set(self.modules))

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(d)) for d in devs) * 1e-9 / len(devs)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def module_runs(self, substring: str) -> list:
        """(start, end, device) of each run of a program whose name holds
        ``substring``, inside the window."""
        return sorted((s, e, d) for d, runs in self.modules.items() for s, e, n in runs
                      if substring in n and s >= self.lo and e <= self.hi)

    def op_time_in(self, lo: float, hi: float, exclude: list = ()) -> float:
        """Seconds in which ops that start in [lo, hi) ran (ops nest, so
        the union of their intervals), leaving out ops that start inside
        any (start, end) of ``exclude``."""
        tot = 0.0
        for runs in self.ops.values():
            end = -1.0
            for s, e, _ in runs:
                if lo <= s < hi and not any(a <= s < b for a, b, *_ in exclude):
                    tot += max(0.0, e - max(s, end))
                    end = max(end, e)
        return tot * 1e-9

    @staticmethod
    def self_times(runs: list) -> list:
        """(start, name, seconds) per op: its time less that of the ops
        nested directly inside it (a loop's body ops nest in the loop)."""
        out, stack = [], []          # stack: indices of open ops
        for s, e, name in runs:
            while stack and runs[stack[-1]][1] <= s:
                stack.pop()
            out.append([s, name, (e - s) * 1e-9])
            if stack:
                out[stack[-1]][2] -= (min(e, runs[stack[-1]][1]) - s) * 1e-9
            stack.append(len(out) - 1)
        return out

    def op_label(self, device: str, start: float, name: str) -> str:
        """``<program>/<op>``: the op's HLO name without its signature, under
        the program that ran it (``?`` where none holds it)."""
        op = name.split(" = ", 1)[0].lstrip("%")[:80]
        runs = self.modules.get(device, [])
        i = bisect.bisect_right(runs, (start, float("inf"))) - 1
        prog = runs[i][2].split("(", 1)[0] if i >= 0 and runs[i][1] > start else "?"
        return f"{prog}/{op}"

    def top_ops(self, n: int = 10) -> list:
        """The device ops that took most time in the window by their self
        time, averaged over the devices: [[program/op, seconds], ...]."""
        tot: dict = {}
        for dev, runs in self.ops.items():
            inside = [r for r in runs if r[0] >= self.lo and r[1] <= self.hi]
            for s, name, secs in self.self_times(inside):
                key = self.op_label(dev, s, name)
                tot[key] = tot.get(key, 0.0) + secs
        ndev = max(len(self.ops), 1)
        return [[k, v / ndev] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest idle gaps of the first device, each named by the
        harness span the host was in at its middle ("host" outside any):
        [[name, seconds], ...]."""
        devs = self.devices()
        if not devs:
            return []
        busy = self.busy_intervals(devs[0])
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        out = []
        for a, b in gaps:
            mid = (a + b) / 2
            inner = [sp for sp in self.spans if sp[0] <= mid < sp[1]]
            name = min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner else "host"
            out.append([name, (b - a) * 1e-9])
        return sorted(out, key=lambda g: -g[1])[:n]

    def span_of(self, t: float):
        """The innermost harness span (start, end, name, step) holding ``t``."""
        inner = [sp for sp in self.spans if sp[0] <= t < sp[1]]
        return min(inner, key=lambda sp: sp[1] - sp[0]) if inner else None
