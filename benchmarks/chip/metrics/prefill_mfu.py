"""Prefill's share of its roofline (%), from the trace.

Least time per admitted prompt is max(FLOPs / peak, bytes / HBM bandwidth)
from ``counts.prefill``: the prompt's matmuls and causal attention, every
weight once, and the prompt's key and value rows written.  Divided by the
device time of the ops that start inside the harness's ``admit`` spans,
leaving out the ``decode_step`` program that each such step also runs.
That device time also holds the engine's scatter of the whole cache into
the slot, which the count leaves out.
"""
import counts


def read(run):
    tr = run.trace
    if tr is None:
        return None
    c = run.ctx.conf["config"]
    decode = [(s, e) for s, e, _ in tr.module_runs("decode_step")]
    least = device = 0.0
    for a, b, name, k in tr.spans:
        if name != "admit" or k is None or a < tr.lo or b > tr.hi:
            continue
        for p in run.steps[k].admitted:
            least += counts.least_time_s(*counts.prefill(c, p, c["torch_dtype"]),
                                         run.ctx.peaks)[0]
        device += tr.op_time_in(a, b, exclude=decode)
    return 100.0 * least / device if device else None
