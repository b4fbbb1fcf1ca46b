"""An MLA + MoE decode step's share of its roofline (%), from the trace.

Least time per step is max(FLOPs / peak, bytes / HBM bandwidth) from
``counts_mla_moe.decode_step``: every weight outside the routed experts
once, the latent rows the live slots hold (their positions, from the
harness's step record), the routed pairs' operations and the expected
bytes of the held experts that the step's tokens chose.  Divided by the
device time of the jitted ``decode_step`` program, summed over the steps of
the traced slice.  None in a cell of another architecture.
"""
import counts_mla_moe


def read(run):
    tr = run.trace
    c = run.ctx.conf["config"]
    if tr is None or "kv_lora_rank" not in c:
        return None
    least = device = 0.0
    for s, e, _ in tr.module_runs("decode_step"):
        span = tr.span_of(s)
        if span is None or span[3] is None:
            continue
        rec = run.steps[span[3]]
        flops, nbytes = counts_mla_moe.decode_step(c, rec.kv_lens, c["torch_dtype"])
        least += counts_mla_moe.least_time_s(flops, nbytes, run.ctx.peaks)[0]
        device += (e - s) * 1e-9
    return 100.0 * least / device if device else None
