"""Time a request waits in the engine's queue (ms), harness clock: p90 of
``start_s - arrival_s``, from the time each request was due to the engine's
start of its admission (``Request.start_s``).

Requests whose wait overlaps the traced slice or the loop's read-out of it
are left out (``chiplib/stamps.py``): those admitted before the slice, and
those due after the read-out, if it ends inside the window.  Prints how
many of each it kept.  None where the engine stamps no ``start_s``.
"""
import sys

from chiplib import stamps
from chiplib.serving import p90


def read(run):
    reqs = stamps.done(run, "start_s")
    if not reqs:
        return None
    lo, hi = stamps.traced(run)
    before = [r.start_s - r.arrival_s for r in reqs if r.start_s < lo]
    after = [r.start_s - r.arrival_s for r in reqs if r.arrival_s > hi]
    print(f"[queue_wait_ms] p90 over {len(before)} requests admitted before the slice and "
          f"{len(after)} due after its read-out, of {len(reqs)}", file=sys.stderr)
    return p90(before + after) * 1e3 if before or after else None
