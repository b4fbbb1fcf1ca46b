"""Model FLOP utilization of training (%), from the trace.

Model FLOPs per token from ``counts.train_flops_per_token`` (6 per matmul
weight plus causal attention, the scores at or below the diagonal only;
recomputation not counted), times the tokens per second of the traced
slice, over chips x peak.  The tokens are those of the ``train_step``
programs that ran in the slice, each counted by the share of its device
time that falls inside it.
"""
import counts


def read(run):
    tr = run.trace
    if tr is None:
        return None
    steps = 0.0
    for runs in tr.modules.values():
        for s, e, name in runs:
            if "train_step" in name and e > s:
                steps += max(0.0, min(e, tr.hi) - max(s, tr.lo)) / (e - s)
    ndev = max(len(tr.modules), 1)
    if not steps:
        return None
    mix, c, peaks = run.ctx.mix, run.ctx.conf["config"], run.ctx.peaks
    tok_s = steps / ndev * run.extra["tokens_per_step"] / tr.window_s
    flops = tok_s * counts.train_flops_per_token(c, mix["seq"])
    return 100.0 * flops / (len(run.ctx.devices) * peaks["bf16_flops_per_s"])
