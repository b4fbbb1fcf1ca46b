"""Engine decode step (ms): the median wall time, on the harness clock, of
the steps that admit nothing (the jitted decode step and one host sync per
slot).  Steps that overlap the profiler's start or stop are left out."""
from statistics import median


def read(run):
    plain = [s.t1 - s.t0 for s in run.quiet_steps() if not s.admitted]
    return median(plain) * 1e3 if plain else None
