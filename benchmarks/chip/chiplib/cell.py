"""One run of one cell: what a driver is given, what it returns, and the
control of the traced slice."""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax

from chiplib import tracing
from chiplib.device import CompileCounter
from chiplib.registry import BENCH_DIR, Registry


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from the whole seed (PRNGKey alone keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


@dataclass
class Ctx:
    """Everything a driver needs, found by name."""
    reg: Registry
    workload: str
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    t_start: float                          # process start, harness clock
    out_dir: Path = BENCH_DIR / ".out"
    cell: dict = field(init=False)
    conf: dict = field(init=False)
    mix: dict = field(init=False)
    ref: object = field(init=False)
    system: object = field(init=False)
    limits: dict = field(init=False)
    compiles: CompileCounter = field(init=False)

    def __post_init__(self):
        self.compiles = CompileCounter()
        self.cell = self.reg.workload(self.workload)
        self.conf = self.reg.config(self.cell["config"])
        self.mix = self.reg.traffic(self.cell["traffic"])
        self.ref = self.reg.module("refs", self.conf["reference"])
        self.system = self.reg.module("systems", self.conf["system"])
        self.limits = self.reg.limits(self.workload)


@dataclass
class StepRec:
    """One step of the window on the harness clock.  Serving steps also
    record the prompt lengths they admitted, the positions each decoding
    slot attended to, and the tokens they produced."""
    t0: float
    t1: float
    admitted: list = field(default_factory=list)
    kv_lens: list = field(default_factory=list)
    tokens: int = 0
    edge: bool = False      # overlaps the start or stop of the trace


@dataclass
class Result:
    """What a driver hands back.  ``e2e``: every end-to-end metric it can
    give; ``checks``: name -> (number, limit), correct when each number is
    at most its limit; ``run``: what the per-layer readers read."""
    e2e: dict
    checks: dict
    attempted: int
    failed: int
    memory_peak_bytes: int
    run: "RunData"


@dataclass
class RunData:
    """What per-layer metric readers read: the harness's step records, the
    reduced trace (None in an untraced run), and the cell's sizes."""
    ctx: Ctx
    steps: list
    trace: tracing.Trace | None
    extra: dict = field(default_factory=dict)

    def quiet_steps(self) -> list:
        """Step records clear of the trace's start and stop."""
        return [s for s in self.steps if not s.edge]


def window_open(ctx: Ctx) -> float:
    """Start counting compiles; the window's start on the harness clock."""
    ctx.compiles.on = True
    return time.perf_counter()


def window_close(ctx: Ctx) -> None:
    ctx.compiles.on = False
    print(f"[window] {ctx.compiles.summary()}", file=sys.stderr)


class TraceSlice:
    """Starts the profiler a share ``AT`` of the window in and stops it the
    mix's ``trace_s`` seconds later (``SECONDS`` where the mix gives none),
    from the driver's loop; steps that overlap either call are marked as
    edges."""

    AT, SECONDS = 0.4, 3.0

    def __init__(self, ctx: Ctx):
        self.on = ctx.trace
        self.dir = ctx.out_dir / f"trace-{ctx.workload}"
        start = self.AT * ctx.seconds
        length = min(ctx.mix.get("trace_s", self.SECONDS), ctx.seconds - start)
        self.start, self.stop = start, start + length
        self.state = "before"
        self.events: list | None = None
        self._span = None
        self.edges: list = []

    def poll(self, rel: float) -> None:
        """Call with the seconds since the window opened."""
        if not self.on:
            return
        if self.state == "before" and rel >= self.start:
            t = time.perf_counter()
            tracing.capture_start(self.dir)
            self._span = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
            self._span.__enter__()
            self.edges.append((t, time.perf_counter()))
            self.state = "tracing"
        elif self.state == "tracing" and rel >= self.stop:
            self.finish()

    def finish(self) -> None:
        if self.state != "tracing":
            return
        t = time.perf_counter()
        self._span.__exit__(None, None, None)
        self.events = tracing.capture_stop(self.dir)
        self.edges.append((t, time.perf_counter()))
        self.state = "done"

    def mark(self, steps: list) -> tracing.Trace | None:
        """Mark edge steps; the reduced trace, or None when not traced."""
        self.finish()
        for s in steps:
            s.edge = any(s.t0 < b and a < s.t1 for a, b in self.edges)
        return tracing.Trace.from_events(self.events) if self.events else None
