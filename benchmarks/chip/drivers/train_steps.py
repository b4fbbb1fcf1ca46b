"""Training on one chip: the program's jitted train step (``launch/train.py``
builds it the same way), its state donated, fed uniform token rows made
on the device from the seed.

Set-up builds the state from the seed and runs the first three steps
through the window's own call and feed, reading what the check compares;
the window then goes on with the same state.  Each step ends in
``float(loss)``, as the program's loop does.  ``train_tok_s`` is the tokens
of the steps completed in the window over the window.
"""
from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chiplib import device, training
from chiplib.cell import (Ctx, Result, RunData, StepRec, TraceSlice, seed_key,
                          window_close, window_open)


def run(ctx: Ctx) -> Result:
    ref, system, c, mix = ctx.ref, ctx.system, ctx.conf["config"], ctx.mix
    mc = system.model_config(ctx.conf)
    dt = jnp.dtype(c["torch_dtype"])
    system.check_layout(mc, jax.eval_shape(lambda k: ref.make_weights(c, k, dt), seed_key(0)))
    step, opt = system.train_step(mc, mix)

    make = jax.jit(lambda k: ref.make_weights(c, k, dt))   # also re-made for the check
    init = jax.jit(lambda w: {"params": system.to_program(w), "step": jnp.zeros((), jnp.int32),
                              "opt": opt.init(system.to_program(w))})
    state = init(make(seed_key(ctx.seed)))
    batch, fkey = training.feed(ctx)
    m_norms = jax.jit(lambda st: ref.leaf_norms(system.from_program(st["opt"]["m"])))
    change = jax.jit(lambda p, w0: ref.leaf_norms(jax.tree.map(
        jnp.subtract, system.from_program(p), w0)))

    prog = {"loss": []}
    for i in range(training.CHECK_STEPS):
        state, met = step(state, batch(fkey, i))
        prog["loss"].append(float(met["loss"]))
        if i == 0:
            prog["grad"] = {k: np.asarray(v, np.float64) / (1 - mix["optimizer"]["b1"])
                            for k, v in m_norms(state).items()}
    prog["change"] = {k: np.asarray(v, np.float64)
                      for k, v in change(state["params"], make(seed_key(ctx.seed))).items()}

    tr = TraceSlice(ctx)
    steps, bad = [], 0
    setup_s = time.perf_counter() - ctx.t_start
    t0 = window_open(ctx)
    i = training.CHECK_STEPS
    while True:
        a = time.perf_counter()
        tr.poll(a - t0)
        with jax.profiler.TraceAnnotation("batch", step=i):
            b = batch(fkey, i)
        with jax.profiler.TraceAnnotation("step", step=i):
            state, met = step(state, b)
            loss = float(met["loss"])
        e = time.perf_counter()
        steps.append(StepRec(a, e))
        bad += not math.isfinite(loss)
        i += 1
        if e - t0 >= ctx.seconds:
            break
    window = steps[-1].t1 - t0
    window_close(ctx)
    trace = tr.mark(steps)
    mem = device.memory_peak_bytes(ctx.devices)
    del state, met, b
    ref_read = training.reference_steps(ctx)
    found = training.compare(prog, ref_read)
    for name, (value, where) in found.items():
        print(f"[check] {name} {value:.6g} at {where}", file=sys.stderr)
    tokens = len(steps) * mix["batch"] * mix["seq"]
    run_data = RunData(ctx, steps, trace, {"tokens_per_step": mix["batch"] * mix["seq"]})
    if trace is not None:
        runs = trace.module_runs("train_step")
        if runs:
            measured = float(np.median([e - s for s, e, _ in runs])) * 1e-9
            pred = system.predict(mc, ctx.devices[0].device_kind, "train", batch=mix["batch"],
                                  seq=mix["seq"], remat=mix["remat"])
            print(f"[sim] train step predicted {pred * 1e3:.3f} ms, measured on the "
                  f"device {measured * 1e3:.3f} ms (median of {len(runs)}), error "
                  f"{(pred - measured) / measured * 100:+.1f}%", file=sys.stderr)
    return Result(
        e2e={"train_tok_s": tokens / window, "setup_s": setup_s},
        checks={k: (v, ctx.limits[k]) for k, (v, _) in found.items() if k in ctx.limits},
        attempted=len(steps), failed=bad, memory_peak_bytes=mem, run=run_data)

