"""The program's named scopes in a traced slice: which share of a program's
device time its ops under one scope took.

The trace's device op events carry no ``op_name`` (their stats are timing
only), but their names are their HLO instructions.  So each op's
``op_name``, which carries the model's and the train step's
``jax.named_scope``s (``embed``, the block kind, ``attn``, ``ffn`` or
``moe``, ``head``; ``optimizer``), is read from the HLO text of the cell's
program, compiled again after the run for the device on the shapes the
cell ran: the compile cache gives the same program back.
"""
from __future__ import annotations

import bisect
import heapq
import re

from chiplib.tracing import Trace

SCOPES = ("optimizer", "attn", "moe", "ffn", "head", "embed")
INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')


def op_names(hlo_text: str) -> dict:
    """Instruction name -> ``op_name``, from a compiled program's HLO text."""
    return dict(m.groups() for m in map(INSTRUCTION.match, hlo_text.splitlines()) if m)


def instruction(op: str) -> str:
    """The instruction name of a device op's trace name (its HLO line)."""
    return op.split(" = ", 1)[0].lstrip("%")


def scope_of(op_name: str, names=SCOPES) -> str:
    """The first of ``names`` among the op_name's scopes, unwrapped from
    transformations (``transpose(jvp(attn_ffn))`` is ``attn_ffn``), else
    ``other``."""
    parts = {re.sub(r"^.*\(", "", p).rstrip(")") for p in op_name.split("/")}
    return next((n for n in names if n in parts), "other")


def program_hlo(ctx, program: str) -> str:
    """The HLO text of the cell's ``decode_step`` or ``train_step``, compiled
    again for the cell's first device on the shapes the cell ran."""
    import jax
    import jax.numpy as jnp

    from chiplib.cell import seed_key
    c, mix, system = ctx.conf["config"], ctx.mix, ctx.system
    mc = system.model_config(ctx.conf)
    one = jax.sharding.SingleDeviceSharding(ctx.devices[0])

    def on(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    dtype = jnp.dtype(c["torch_dtype"])
    params = system.to_program(jax.eval_shape(lambda k: ctx.ref.make_weights(c, k, dtype),
                                              seed_key(0)))
    if program == "decode_step":
        eng = system.engine(mc, params, slots=mix["slots"], cache_len=mix["cache_len"])
        low = eng._decode.lower(on(params), eng.cache, {"tokens": eng._last_tok})
    elif program == "train_step":
        step, opt = system.train_step(mc, mix)
        state = {"params": params, "step": jax.ShapeDtypeStruct((), jnp.int32),
                 "opt": jax.eval_shape(opt.init, params)}
        rows = jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32)
        low = step.lower(on(state), on({"tokens": rows, "labels": rows}))
    else:
        raise ValueError(program)
    return low.compile().as_text()


def program_names(ctx, program: str) -> dict:
    """Instruction name -> ``op_name`` of the cell's ``program``."""
    return op_names(program_hlo(ctx, program))


def own_times(ops: list) -> list:
    """Seconds per op of ``ops`` ((start, end, name), sorted) in which it is
    the innermost op running: each instant goes to the latest-starting op
    that holds it, the one that ends first among those that start
    together.  Where ops nest this is ``Trace.self_times``; a loop whose
    run outlasts an async copy it started inside is not counted twice."""
    out = [0.0] * len(ops)
    edges = sorted({t for s, e, _ in ops for t in (s, e)})
    heap: list = []
    k = 0
    for a, b in zip(edges, edges[1:]):
        while k < len(ops) and ops[k][0] <= a:
            heapq.heappush(heap, (-ops[k][0], ops[k][1], k))
            k += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            out[heap[0][2]] += (b - a) * 1e-9
    return out


def self_time_by_scope(tr: Trace, program: str, names: dict, scopes=SCOPES) -> dict:
    """Seconds of device time, summed over the devices, by scope, of the ops
    that start inside a run of ``program`` in the slice, each instant given
    to the innermost op (``own_times``; ``names``: instruction name ->
    op_name)."""
    out: dict = {}
    for dev, ops in tr.ops.items():
        runs = [(s, e) for s, e, d in tr.module_runs(program) if d == dev]
        starts = [s for s, _ in runs]
        mine = [(s, e, n) for s, e, n in ops
                if (i := bisect.bisect_right(starts, s) - 1) >= 0 and s < runs[i][1]]
        for (_, _, n), secs in zip(mine, own_times(mine)):
            k = scope_of(names.get(instruction(n), ""), scopes)
            out[k] = out.get(k, 0.0) + secs
    return out


def share(run, program: str, scope: str) -> float | None:
    """``scope``'s share (%) of the device time of ``program``'s runs in the
    slice; None where the slice holds no run of it or the program
    names no such scope."""
    tr = run.trace
    if tr is None or not tr.module_runs(program):
        return None
    names = program_names(run.ctx, program)
    if not any(scope_of(n, (scope,)) == scope for n in names.values()):
        return None
    by = self_time_by_scope(tr, program, names)
    tot = sum(by.values())
    return 100.0 * by.get(scope, 0.0) / tot if tot else None
