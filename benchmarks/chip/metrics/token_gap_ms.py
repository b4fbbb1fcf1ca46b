"""Time between a request's successive tokens (ms), harness clock: p90 over
every gap between two readings of ``Request.token_s`` of every request.

A gap is one engine step as the request sees it: the decode step, and the
admission of any other request in the same step.  Gaps that overlap the
traced slice or the loop's read-out of it are left out
(``chiplib/stamps.py``).  None where the engine stamps no ``token_s``.
"""
from chiplib import stamps
from chiplib.serving import p90


def read(run):
    reqs = stamps.done(run, "token_s")
    if not reqs:
        return None
    lo, hi = stamps.traced(run)
    gaps = [b - a for r in reqs for a, b in zip(r.token_s, r.token_s[1:])
            if b < lo or a > hi]
    return p90(gaps) * 1e3 if gaps else None
