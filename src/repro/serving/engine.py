"""Serving engine: continuous batching over slot-based KV caches.

``ServingEngine`` keeps B cache slots; requests are admitted into free slots
(prefill populates the slot via the model's prefill path at batch=1, then the
KV rows are scattered into the slot), and every engine step decodes one token
for all active slots.  Per-slot positions make mixed-depth batches exact.
SLO accounting (TTFT/TPOT per request) feeds the explorer's Pareto search.

All timestamps flow through one injected ``clock`` (default: wall clock).
Trace replay passes a :class:`~repro.serving.sim.workload.VirtualClock`
driven in simulated seconds, so caller-supplied ``arrival_s`` values —
including ``0.0`` — are honored exactly and TTFT/finish times stay on the
trace's timebase instead of mixing in ``perf_counter`` readings.

On the device path the engine marks its own work on the profiler's
timeline with ``jax.profiler.TraceAnnotation`` spans, which cost about a
microsecond each when no profiler runs.  Per admitted request:
``engine.admit`` (args ``rid``, ``slot``, ``prompt_len``, ``queued``: the
requests still waiting) holding, in order, ``engine.prefill`` (args ``rid``
and ``compiled``: the dispatch of the jitted batch-1 prefill, and, where
``compiled`` is true, its one compile for a prompt length the engine had
not prefilled before), ``engine.first_token`` (the wait for the prefill's
device work and the host read of its first token, the TTFT stamp) and
``engine.scatter`` (its cache rows into the slot).  Per decode step:
``engine.decode`` (arg ``active``) holding ``engine.sample`` (arg
``tokens``: the argmax and one host read of every live slot's token).
``docs/observability.md`` says how to capture them.

``ServingEngine.host_reads`` counts the engine's device-to-host reads: one
per admission (its first token) and one per decode step, whatever the
number of live slots.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import Model, zero_cache


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16
    arrival_s: float | None = None   # None: stamped by the engine's clock
    # outputs, stamped on the engine's clock
    tokens: list[int] = field(default_factory=list)
    start_s: float | None = None     # its admission began (SimRequest.start_s)
    token_s: list[float] = field(default_factory=list)   # one per token
    ttft_s: float | None = None      # token_s[0] - arrival_s
    finished_s: float | None = None
    slot: int | None = None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 cache_len: int = 512, greedy: bool = True,
                 clock: Callable[[], float] = time.perf_counter):
        self.cfg = cfg
        self.clock = clock
        self.model = Model(cfg)
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.cache = zero_cache(cfg, slots, cache_len)
        self.cache["pos"] = jnp.zeros((slots,), jnp.int32)
        self.active: dict[int, Request] = {}     # slot -> request
        self.queue: list[Request] = []
        self.greedy = greedy
        self._decode = jax.jit(self.model.decode_step)
        # one program per prompt length, kept by jit's cache (keyed by shape)
        self._prefill = jax.jit(functools.partial(self.model.prefill,
                                                  cache_len=cache_len))
        self._prefilled: set[int] = set()        # prompt lengths seen
        self._last_tok = jnp.zeros((slots, 1), jnp.int32)
        self.finished: list[Request] = []
        self.host_reads = 0                      # device-to-host reads made

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        if req.arrival_s is None:    # explicit 0.0 (trace replay) is kept
            req.arrival_s = self.clock()
        self.queue.append(req)

    def _free_slots(self) -> list[int]:
        return [s for s in range(self.slots) if s not in self.active]

    def _admit(self):
        for slot in self._free_slots():
            if not self.queue:
                break
            req = self.queue.pop(0)
            req.slot = slot
            req.start_s = self.clock()
            n = len(req.prompt)
            with TraceAnnotation("engine.admit", rid=req.rid, slot=slot,
                                 prompt_len=n, queued=len(self.queue)):
                with TraceAnnotation("engine.prefill", rid=req.rid,
                                     compiled=n not in self._prefilled):
                    self._prefilled.add(n)
                    prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
                    logits, pc = self._prefill(self.params, {"tokens": prompt})
                with TraceAnnotation("engine.first_token", rid=req.rid):
                    tok = int(jnp.argmax(logits[0, -1]))
                    self.host_reads += 1
                    now = self.clock()
                    req.tokens.append(tok)
                    req.token_s.append(now)
                    req.ttft_s = now - req.arrival_s
                with TraceAnnotation("engine.scatter", rid=req.rid):
                    self._scatter(slot, pc, n, tok)
            self.active[slot] = req

    def _scatter(self, slot: int, pc: dict, pos: int, tok: int):
        """The single-request (batch=1) cache into ``slot``, its position and
        its last token, for every cache group (``cycle`` leaves are
        layer-stacked: batch is dim 1; ``lead`` and ``tail``: dim 0)."""
        for group, own in pc["blocks"].items():
            at = (slice(None),) if group == "cycle" else ()
            self.cache["blocks"][group] = jax.tree.map(
                lambda c, o: c.at[(*at, slot)].set(o[(*at, 0)]), self.cache["blocks"][group], own)
        self.cache["pos"] = self.cache["pos"].at[slot].set(pos)
        self._last_tok = self._last_tok.at[slot, 0].set(tok)

    # ------------------------------------------------------------------
    def step(self) -> int:
        """Admit + one decode step for all active slots.  Returns #active."""
        self._admit()
        if not self.active:
            return 0
        with TraceAnnotation("engine.decode", active=len(self.active)):
            logits, self.cache = self._decode(self.params, self.cache,
                                              {"tokens": self._last_tok})
            with TraceAnnotation("engine.sample", tokens=len(self.active)):
                next_tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                self._last_tok = next_tok[:, None]
                toks = jax.device_get(next_tok).tolist()   # one read, every slot
                self.host_reads += 1
            now = self.clock()
            done = []
            for slot, req in self.active.items():
                req.tokens.append(toks[slot])
                req.token_s.append(now)
                if len(req.tokens) >= req.max_new_tokens:
                    req.finished_s = now
                    done.append(slot)
            for slot in done:
                self.finished.append(self.active.pop(slot))
        return len(self.active) + len(done)

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            self.step()
            steps += 1
        return self.finished
