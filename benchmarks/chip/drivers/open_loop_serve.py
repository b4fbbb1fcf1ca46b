"""Open-loop serving: independent users send requests on a Poisson schedule
at the mix's fixed rate, whether or not earlier ones have finished.

All requests are due inside the window; once the last is sent the harness
drains what is in flight, so every request due in the window completes
and is checked.  Each request is timed from when it was due to its first
token (TTFT); the tail is p90 over all requests due in the window.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from chiplib import device, serving, traffic
from chiplib.cell import Ctx, Result, RunData, TraceSlice, window_close, window_open


def schedule(mix: dict, seed: int, seconds: float, vocab: int):
    """Due times, prompts and output lengths of the window's requests."""
    due = traffic.poisson_arrivals(mix["rate_per_s"], seconds, traffic.rng(seed, 1))
    n = len(due)
    plen = traffic.lengths(mix["prompt"], n, traffic.rng(seed, 2))
    outs = traffic.lengths(mix["output"], n, traffic.rng(seed, 3))
    return due, traffic.prompts(plen, vocab, traffic.rng(seed, 4)), outs


def run(ctx: Ctx) -> Result:
    srv = serving.Server(ctx)
    srv.warm()
    due, prompts, outs = schedule(ctx.mix, ctx.seed, ctx.seconds, srv.vocab)
    n = len(due)
    tr = TraceSlice(ctx)
    setup_s = time.perf_counter() - ctx.t_start
    t0 = window_open(ctx)
    i = 0
    while True:
        rel = time.perf_counter() - t0
        while i < n and due[i] <= rel:
            srv.submit(i, prompts[i], outs[i], t0 + due[i])
            i += 1
        tr.poll(rel)
        if srv.busy():
            srv.step()
        elif i < n:
            time.sleep(max(0.0, due[i] - rel))
        else:
            break
    window_close(ctx)
    trace = tr.mark(srv.steps)
    mem = device.memory_peak_bytes(ctx.devices)
    steps = srv.steps
    done = srv.free()

    ttft = [r.ttft_s * 1e3 for r in done]
    wrong = serving.wrong_outputs(done, srv.vocab)
    picked = serving.sample(done, ctx.seed, ctx.mix["check_tokens"])
    gaps = serving.widest_gaps(ctx, picked, srv.cache_len)
    print(f"[check] {len(picked)} requests, {sum(len(r.tokens) for r in picked)} served "
          f"tokens compared; widest gap per request {np.round(gaps, 4).tolist()}",
          file=sys.stderr)
    print(f"[occupancy] {serving.occupancy(steps, t0, srv.slots, srv.cache_len)}",
          file=sys.stderr)
    serving.report_prediction(ctx, trace, srv)
    return Result(
        e2e={"ttft_p90_ms": serving.p90(ttft), "setup_s": setup_s},
        checks={"logit_gap": (max(gaps), ctx.limits["logit_gap"]),
                "unfinished": (n - len(done), 0), "wrong_outputs": (wrong, 0)},
        attempted=n, failed=n - len(done) + wrong, memory_peak_bytes=mem,
        run=RunData(ctx, steps, trace, {"sample": picked, "done": done, "t0": t0}))
