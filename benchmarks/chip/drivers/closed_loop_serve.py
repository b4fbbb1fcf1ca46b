"""Closed-loop serving: a fixed number of clients, each sending its next
request as soon as its last one has finished (reasoning and agent users
who wait for each answer).

Client c's first request asks for (c + 0.5) / clients of the c-th of the
clients' quantiles of the output length (the same for every seed), so
that the clients are out of phase from the start, as in a steady state.
The window ends at the first step boundary past ``seconds``; requests
still in flight are not drained, since ``serve_tok_s`` is the tokens
produced in the window over the window.  The check compares requests
finished in the window.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from chiplib import device, serving, traffic
from chiplib.cell import Ctx, Result, RunData, TraceSlice, window_close, window_open


def schedule(mix: dict, seed: int, vocab: int):
    """Per client, its queue of (prompt, output length)."""
    C, per = mix["clients"], mix["requests_per_client"]
    plen = traffic.lengths(mix["prompt"], C * per, traffic.rng(seed, 2))
    outs = traffic.lengths(mix["output"], C * per, traffic.rng(seed, 3))
    prompts = traffic.prompts(plen, vocab, traffic.rng(seed, 4))
    first = traffic.quantile_lengths(mix["output"], C)
    for c in range(C):
        outs[c * per] = max(2, round(first[c] * (c + 0.5) / C))
    return [[(prompts[c * per + k], outs[c * per + k]) for k in range(per)] for c in range(C)]


def run(ctx: Ctx) -> Result:
    srv = serving.Server(ctx)
    srv.warm()
    queues = schedule(ctx.mix, ctx.seed, srv.vocab)
    owner, sent = {}, 0

    def send(c: int, now: float) -> None:
        nonlocal sent
        if not queues[c]:
            print(f"[warn] client {c} ran out of requests", file=sys.stderr)
            return
        prompt, out = queues[c].pop(0)
        owner[sent] = c
        srv.submit(sent, prompt, out, now)
        sent += 1

    tr = TraceSlice(ctx)
    setup_s = time.perf_counter() - ctx.t_start
    t0 = window_open(ctx)
    for c in range(len(queues)):
        send(c, t0)
    while True:
        now = time.perf_counter()
        if now - t0 >= ctx.seconds:
            break
        tr.poll(now - t0)
        n_fin = len(srv.engine.finished)
        srv.step()
        now = time.perf_counter()
        for r in srv.engine.finished[n_fin:]:
            send(owner[r.rid], now)
    window = srv.steps[-1].t1 - t0
    window_close(ctx)
    trace = tr.mark(srv.steps)
    mem = device.memory_peak_bytes(ctx.devices)
    steps = srv.steps
    done = srv.free()

    tokens = sum(s.tokens for s in steps)
    wrong = serving.wrong_outputs(done, srv.vocab)
    picked = serving.sample(done, ctx.seed, ctx.mix["check_tokens"])
    gaps = serving.widest_gaps(ctx, picked, srv.cache_len)
    print(f"[check] {len(picked)} of {len(done)} finished requests, "
          f"{sum(len(r.tokens) for r in picked)} served tokens compared; widest gap "
          f"per request {np.round(gaps, 4).tolist()}", file=sys.stderr)
    print(f"[occupancy] {serving.occupancy(steps, t0, srv.slots, srv.cache_len)}",
          file=sys.stderr)
    serving.report_prediction(ctx, trace, srv)
    return Result(
        e2e={"serve_tok_s": tokens / window, "setup_s": setup_s},
        checks={"logit_gap": (max(gaps) if gaps else float("inf"), ctx.limits["logit_gap"]),
                "wrong_outputs": (wrong, 0)},
        attempted=sent, failed=wrong, memory_peak_bytes=mem,
        run=RunData(ctx, steps, trace, {"sample": picked}))
