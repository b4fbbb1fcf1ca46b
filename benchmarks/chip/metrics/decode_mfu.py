"""The whole decode step's share of its roofline (%), from the trace.

Least time per step is max(FLOPs / peak, bytes / HBM bandwidth) from
``counts.decode_step``: every weight once, and only the key and value rows
the active slots hold (their positions, from the harness's step record),
not the whole allocated cache.  Divided by the device time of the jitted
``decode_step`` program, summed over the steps of the traced slice.
"""
import counts


def read(run):
    tr = run.trace
    if tr is None:
        return None
    c = run.ctx.conf["config"]
    least = device = 0.0
    for s, e, _ in tr.module_runs("decode_step"):
        span = tr.span_of(s)
        if span is None or span[3] is None:
            continue
        rec = run.steps[span[3]]
        flops, nbytes = counts.decode_step(c, rec.kv_lens, c["torch_dtype"])
        least += counts.least_time_s(flops, nbytes, run.ctx.peaks)[0]
        device += (e - s) * 1e-9
    return 100.0 * least / device if device else None
