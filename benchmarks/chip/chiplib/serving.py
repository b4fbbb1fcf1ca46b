"""Serving machinery shared by the serving drivers: the engine built from a
configuration, its warm-up, one timed engine step, and the comparison of
served tokens with the reference.

The weights are the benchmark's (``refs/<reference>.make_weights`` from the
seed, in the configuration's dtype, one jitted call on the device); the
engine is the program's ``ServingEngine``, driven only through ``submit``
and ``step``.
"""
from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chiplib import traffic
from chiplib.cell import Ctx, StepRec, seed_key


class Server:
    """The program's engine on the cell's weights, and the record of each
    step it took."""

    def __init__(self, ctx: Ctx):
        self.ctx, mix = ctx, ctx.mix
        self.slots, self.cache_len = mix["slots"], mix["cache_len"]
        self.steps: list[StepRec] = []
        c = ctx.conf["config"]
        mc = ctx.system.model_config(ctx.conf)
        dtype = jnp.dtype(c["torch_dtype"])
        ctx.system.check_layout(mc, jax.eval_shape(
            lambda k: ctx.ref.make_weights(c, k, dtype), seed_key(0)))
        params = ctx.system.to_program(jax.block_until_ready(weights(ctx)))
        self.engine = ctx.system.engine(mc, params, slots=self.slots,
                                        cache_len=self.cache_len)
        self.vocab = c["vocab_size"]
        self.model_config = mc

    def warm(self) -> None:
        """Compile every shape the mix can send: each prompt bucket, each
        slot, the decode step.  Two rounds fill every slot at once."""
        lens = traffic.buckets(self.ctx.mix["prompt"])
        gen = traffic.rng(self.ctx.seed, 99)
        for r in range(2):
            for s in range(self.slots):
                n = lens[(s + r * self.slots) % len(lens)]
                self.submit(-1 - s - r * self.slots, gen.integers(0, self.vocab, n).tolist(),
                            2, time.perf_counter())
            self.drain()
        self.engine.finished.clear()
        self.steps.clear()

    def submit(self, rid: int, prompt: list, max_new: int, arrival_s: float) -> None:
        self.engine.submit(self.ctx.system.request(rid, prompt, max_new, arrival_s))

    def busy(self) -> bool:
        return bool(self.engine.queue or self.engine.active)

    def step(self) -> StepRec:
        """One engine step (admission and a decode), inside a harness span
        named ``admit`` where it admits and ``decode`` where it does not."""
        eng = self.engine
        before = {id(r) for r in eng.active.values()}
        n_fin = len(eng.finished)
        name = "admit" if eng.queue and len(eng.active) < self.slots else "decode"
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name, step=len(self.steps)):
            eng.step()
        t1 = time.perf_counter()
        went = list(eng.active.values()) + eng.finished[n_fin:]
        admitted = [len(r.prompt) for r in went if id(r) not in before]
        rec = StepRec(t0, t1, admitted, [len(r.prompt) + len(r.tokens) - 1 for r in went],
                      len(went) + len(admitted))
        self.steps.append(rec)
        return rec

    def drain(self) -> None:
        while self.busy():
            self.step()

    def free(self) -> list:
        """Drop the engine and its weights; return the finished requests."""
        done = list(self.engine.finished)
        self.engine = None
        gc.collect()
        return done


def weights(ctx: Ctx) -> dict:
    """The cell's weights from the seed, in the configuration's dtype: one
    jitted program, the same for the engine and for the reference."""
    c = ctx.conf["config"]
    dtype = jnp.dtype(c["torch_dtype"])
    return jax.jit(lambda k: ctx.ref.make_weights(c, k, dtype))(seed_key(ctx.seed))


# ---------------------------------------------------------------- checking

def wrong_outputs(reqs: list, vocab: int) -> int:
    """Finished requests with another number of tokens than asked, or a
    token outside the vocabulary."""
    return sum(len(r.tokens) != r.max_new_tokens or
               any(not 0 <= t < vocab for t in r.tokens) for r in reqs)


def sample(reqs: list, seed: int, min_tokens: int) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, until ``min_tokens`` served tokens are in the sample."""
    if not reqs:
        return []
    order = sorted(reqs, key=lambda r: (-(len(r.prompt) + len(r.tokens)), r.rid))
    rest = [order[i] for i in traffic.rng(seed, 7).permutation(range(1, len(order)))]
    out, n = [], 0
    for r in [order[0]] + rest:
        out.append(r)
        n += len(r.tokens)
        if n >= min_tokens:
            break
    return out


def rows(reqs: list, length: int) -> tuple[np.ndarray, np.ndarray]:
    """Teacher-forced rows: the prompt and the served tokens but the last,
    with each served token as the target of the position that made it;
    padded at the end to ``length`` (targets -1 there)."""
    toks = np.zeros((len(reqs), length), np.int32)
    tgt = np.full((len(reqs), length), -1, np.int32)
    for i, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.tokens[:-1])
        toks[i, :len(seq)] = seq
        p = len(r.prompt)
        tgt[i, p - 1:p - 1 + len(r.tokens)] = r.tokens
    return toks, tgt


def widest_gaps(ctx: Ctx, reqs: list, length: int, *, control: bool = False) -> list:
    """Per sampled request, the widest gap by which a served token's logit
    lies below the reference's best (``control``: the gap of the token the
    fp8 control puts first), one row at a time on weights made anew from
    the seed."""
    c = ctx.conf["config"]
    w = weights(ctx)
    fn = ctx.ref.control_gaps if control else ctx.ref.served_gaps
    gap = jax.jit(lambda w, t, g: fn(c, w, t, g).max())
    toks, tgt = rows(reqs, length)
    out = [float(gap(w, toks[i:i + 1], tgt[i:i + 1])) for i in range(len(reqs))]
    del w
    gc.collect()
    return out


def occupancy(steps: list, t0: float, slots: int, cache_len: int) -> str:
    """The live slots and the share of the KV cache they hold, averaged
    over the time from ``t0`` to the last step's end (idle time counts as
    empty), and the most slots live at once."""
    if not steps:
        return "no steps"
    span = steps[-1].t1 - t0
    live = sum((s.t1 - s.t0) * len(s.kv_lens) for s in steps) / span
    kv = sum((s.t1 - s.t0) * sum(s.kv_lens) for s in steps) / span
    return (f"{live:.2f} of {slots} slots live on average (at most "
            f"{max(len(s.kv_lens) for s in steps)}), holding "
            f"{100 * kv / (slots * cache_len):.2f} % of the KV cache's rows")


def p90(values: list) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 90))


def report_prediction(ctx: Ctx, trace, srv: Server) -> None:
    """In a traced run, print the simulator's predicted decode step for the
    cell beside the decode program's median device time."""
    runs = trace.module_runs("decode_step") if trace is not None else []
    if not runs:
        return
    measured = float(np.median([e - s for s, e, _ in runs])) * 1e-9
    pred = ctx.system.predict(srv.model_config, ctx.devices[0].device_kind, "decode",
                              batch=srv.slots, cache_len=srv.cache_len)
    print(f"[sim] decode step (batch {srv.slots}, cache {srv.cache_len}) predicted "
          f"{pred * 1e3:.3f} ms, measured on the device {measured * 1e3:.3f} ms (median "
          f"of {len(runs)}), error {(pred - measured) / measured * 100:+.1f}%",
          file=sys.stderr)
