"""The serving engine's per-request stamps (``Request.start_s``, one
``Request.token_s`` reading per token), on the engine's clock, which is
the harness's, read clear of the traced slice.

The loop starts the profiler where ``TraceSlice`` says and reads the trace
back at its stop, before its next step: requests wait through both, and
a traced chat run's read-out can run past the window's end.  So readers
leave out what overlaps ``traced(run)``: from the slice's start to the
first step after its stop (no end where no step follows).
"""
from __future__ import annotations

from chiplib.cell import TraceSlice


def traced(run) -> tuple:
    """(start, end) on the harness clock of the slice and its read-out."""
    t0, sl = run.extra["t0"], TraceSlice(run.ctx)
    end = min((s.t0 for s in run.steps if s.t0 >= t0 + sl.stop), default=float("inf"))
    return t0 + sl.start, end


def done(run, stamp: str) -> list:
    """The run's finished requests, or [] where the engine keeps no such
    stamp."""
    reqs = run.extra.get("done") or []
    return reqs if reqs and getattr(reqs[0], stamp, None) is not None else []
