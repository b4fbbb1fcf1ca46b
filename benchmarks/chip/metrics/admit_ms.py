"""Engine admission time per request admitted (ms), harness clock.

Steps that admit run the eager batch-1 prefill, the cache scatter and the
host sync of each admitted request, and then the same decode step as any
other: (wall time of the admitting steps - their count x the median step
that admits nothing) / requests admitted.  Steps that overlap the
profiler's start or stop are left out.
"""
from statistics import median


def read(run):
    steps = run.quiet_steps()
    plain = [s.t1 - s.t0 for s in steps if not s.admitted]
    admit = [s for s in steps if s.admitted]
    if not plain or not admit:
        return None
    extra = sum(s.t1 - s.t0 for s in admit) - len(admit) * median(plain)
    return extra / sum(len(s.admitted) for s in admit) * 1e3
