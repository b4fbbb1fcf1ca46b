"""Graph tracer: native JAX model ingestion (paper §3.2a).

Charon ingests HuggingFace/vLLM/PyTorch models via torch.fx; the JAX-native
equivalent is the jaxpr.  ``trace(fn, *args)`` turns ANY jax-traceable
callable (our model zoo, a train step, a serving step, user code) into an
operator-level :class:`~repro.core.ir.Graph` — no hand-crafted workload
description.  Backward graphs come from ``jax.vjp`` (the aot_autograd
analogue).  ``lax.scan`` sub-jaxprs are traced once and emitted with a
``repeat`` multiplier — the paper's single-block extrapolation.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax.extend import core as jex_core

from repro.core.ir import Graph, OpNode

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int32": 4,
                "uint32": 4, "int8": 1, "uint8": 1, "bool": 1, "float64": 8,
                "int64": 8, "uint64": 8, "float8_e4m3fn": 1, "float8_e5m2": 1}
_DTYPE_SHORT = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
                "int8": "int8", "float8_e4m3fn": "f8", "float8_e5m2": "f8"}

ELEMENTWISE = {
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "sign", "floor",
    "ceil", "round", "clamp", "select_n", "convert_element_type", "and", "or",
    "not", "xor", "eq", "ne", "lt", "le", "gt", "ge", "add_any", "rem",
    "stop_gradient", "copy", "real", "imag", "is_finite", "nextafter",
    "shift_left", "shift_right_logical", "shift_right_arithmetic", "erf_inv",
}
TRANSCENDENTAL = {"exp", "log", "tanh", "logistic", "rsqrt", "sqrt", "pow",
                  "integer_pow", "sin", "cos", "erf", "cbrt", "log1p", "expm1",
                  "atan2", "exp2", "square"}
MOVEMENT = {"broadcast_in_dim": "copy", "reshape": "copy", "squeeze": "copy",
            "transpose": "transpose", "rev": "copy", "slice": "copy",
            "dynamic_slice": "copy", "concatenate": "copy", "pad": "copy",
            "dynamic_update_slice": "scatter", "gather": "gather",
            "scatter": "scatter", "scatter-add": "scatter", "scatter_add": "scatter",
            "sort": "sort", "argsort": "sort", "iota": "copy", "expand_dims": "copy"}
REDUCTION = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
             "reduce_and", "reduce_or", "argmax", "argmin", "reduce_precision",
             "cumsum", "cummax", "cumprod", "cumlogsumexp"}
COMM = {"psum": "all_reduce", "all_gather": "all_gather",
        "psum_scatter": "reduce_scatter", "reduce_scatter": "reduce_scatter",
        "all_to_all": "all_to_all", "ppermute": "collective_permute"}
INLINE = {"pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
          "custom_vjp_call_jaxpr", "remat", "checkpoint", "remat2",
          "custom_jvp_call_jaxpr", "core_call", "xla_call", "sharding_constraint",
          "mesh_cast", "shard_map", "device_put"}


def _aval_bytes(aval) -> float:
    # math.prod over the (small, int) shape tuple: ~30x cheaper than np.prod
    # on the thousands of per-eqn calls a block trace makes
    try:
        return float(math.prod(aval.shape)) * _DTYPE_BYTES.get(str(aval.dtype), 4)
    except Exception:
        return 0.0


def _aval_elems(aval) -> float:
    try:
        return float(math.prod(aval.shape))
    except Exception:
        return 0.0


def _short_dtype(aval) -> str:
    return _DTYPE_SHORT.get(str(getattr(aval, "dtype", "bfloat16")), "f32")


class _TraceCtx:
    def __init__(self, graph: Graph):
        self.graph = graph
        self.producer: dict[Any, str] = {}

    def dep_of(self, var) -> str | None:
        return self.producer.get(var)


def _dot_general_node(ctx: _TraceCtx, eqn, mult: float, phase: str):
    """A matmul node.  ``ragged_dot_general`` (the experts' grouped matmul)
    counts its lhs rows once, each against its own group's matrix; the
    node carries ``ragged``."""
    ragged = "ragged_dot_dimension_numbers" in eqn.params
    if ragged:
        rdn = eqn.params["ragged_dot_dimension_numbers"]
        (lc, rc), (lb, rb) = rdn.dot_dimension_numbers
        rb = tuple(rb) + tuple(rdn.rhs_group_dimensions)
    else:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    contract = 1
    for d in lc:
        contract *= lhs.shape[d]
    out_elems = _aval_elems(out)
    flops = 2.0 * out_elems * contract
    if ragged and set(rdn.lhs_ragged_dimensions) & set(lc):
        # ragged contraction (the experts' weight gradient): the output has
        # a block per group, each summing over that group's rows only
        flops /= eqn.invars[2].aval.shape[-1]
    # (M, N, K) for the MXU-alignment model
    n = 1
    for d in range(len(rhs.shape)):
        if d not in rc and d not in rb:
            n *= rhs.shape[d]
    m = out_elems / max(n, 1)
    node = ctx.graph.op(
        "matmul", deps=[d for v in eqn.invars if (d := ctx.dep_of(v))],
        out_shape=tuple(out.shape), dtype=_short_dtype(out),
        flops=flops,
        bytes_in=sum(_aval_bytes(v.aval) for v in eqn.invars),
        bytes_out=_aval_bytes(out),
        repeat=int(mult), phase=phase,
        attrs={"mm_dims": (int(m), int(n), int(contract)),
               "mm_bytes": (_aval_bytes(lhs), _aval_bytes(rhs))},
    )
    if ragged:
        node.attrs["ragged"] = True
    return node


def _trace_jaxpr(ctx: _TraceCtx, jaxpr, mult: float, phase: str):
    g = ctx.graph
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        # structural prims first: they recurse and never consume the per-eqn
        # byte accounting, so skip building it (pjit eqns dominate raw jaxprs)
        if prim == "scan":
            length = eqn.params.get("length", 1)
            inner = eqn.params["jaxpr"].jaxpr
            for v_outer, v_inner in zip(eqn.invars, inner.invars):
                if not isinstance(v_outer, jex_core.Literal) and ctx.dep_of(v_outer):
                    ctx.producer[v_inner] = ctx.dep_of(v_outer)
            _trace_jaxpr(ctx, inner, mult * length, phase)
            for v_outer, v_inner in zip(eqn.outvars, inner.outvars):
                if not isinstance(v_inner, jex_core.Literal) and ctx.dep_of(v_inner):
                    ctx.producer[v_outer] = ctx.dep_of(v_inner)
            continue
        if prim == "while":
            _trace_jaxpr(ctx, eqn.params["body_jaxpr"].jaxpr, mult, phase)
            continue
        if prim == "cond":
            _trace_jaxpr(ctx, eqn.params["branches"][0].jaxpr, mult, phase)
            continue
        if prim in INLINE:
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr") \
                or eqn.params.get("fun_jaxpr")
            if sub is None:
                continue
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            for v_outer, v_inner in zip(eqn.invars, inner.invars):
                if not isinstance(v_outer, jex_core.Literal) and ctx.dep_of(v_outer):
                    ctx.producer[v_inner] = ctx.dep_of(v_outer)
            _trace_jaxpr(ctx, inner, mult, phase)
            for v_outer, v_inner in zip(eqn.outvars, inner.outvars):
                if not isinstance(v_inner, jex_core.Literal) and ctx.dep_of(v_inner):
                    ctx.producer[v_outer] = ctx.dep_of(v_inner)
            continue
        deps = [d for v in eqn.invars
                if not isinstance(v, jex_core.Literal) and (d := ctx.dep_of(v))]
        out = eqn.outvars[0].aval if eqn.outvars else None
        common = dict(deps=deps,
                      out_shape=tuple(getattr(out, "shape", ()) or ()),
                      dtype=_short_dtype(out) if out is not None else "f32",
                      bytes_in=sum(_aval_bytes(v.aval) for v in eqn.invars
                                   if not isinstance(v, jex_core.Literal)),
                      bytes_out=sum(_aval_bytes(v.aval) for v in eqn.outvars),
                      repeat=int(mult), phase=phase)
        node = None
        if prim in ("charon_attention", "charon_attention_bwd"):
            from repro.core.stubs import attention_flops
            q, k, v = (eqn.invars[i].aval for i in range(3))
            causal = eqn.params.get("causal", True)
            window = eqn.params.get("window", 0)
            fl = attention_flops(q.shape, v.shape, causal=causal, window=window)
            if prim.endswith("bwd"):
                fl *= 2.5  # dq/dk/dv + score recompute
            b, sq, hkv, g_, dq = q.shape
            node = g.op("attention", flops=fl, **common)
            node.attrs["attn_dims"] = (int(b), int(hkv * g_), int(sq),
                                       int(v.shape[1]), int(dq))
            node.attrs["causal"], node.attrs["window"] = causal, window
        elif prim in ("dot_general", "ragged_dot_general"):
            node = _dot_general_node(ctx, eqn, mult, phase)
        elif prim in ("conv_general_dilated",):
            out_elems = _aval_elems(out)
            k = eqn.invars[1].aval
            kernel_elems = _aval_elems(k) / max(k.shape[-1], 1)
            node = g.op("conv", flops=2.0 * out_elems * kernel_elems, **common)
        elif prim in COMM:
            axis = eqn.params.get("axes") or eqn.params.get("axis_name") or ("?",)
            axis = axis[0] if isinstance(axis, tuple) and axis else axis
            node = g.op(COMM[prim], comm_bytes=common["bytes_out"],
                        comm_group=str(axis), **common)
        elif prim in REDUCTION:
            node = g.op("reduce", flops=sum(_aval_elems(v.aval) for v in eqn.invars
                                            if not isinstance(v, jex_core.Literal)),
                        **common)
        elif prim in MOVEMENT:
            kind = MOVEMENT[prim]
            if prim in ("slice", "dynamic_slice", "gather"):
                # slices/gathers read the extracted elements, not the operand
                # (embedding lookups must not be priced as full-table reads)
                common = dict(common, bytes_in=common["bytes_out"])
            if kind == "scatter" and len(eqn.invars) >= 2:
                # in-place update semantics (XLA donates/aliases the operand):
                # traffic = read+write of the UPDATE slice + indices, not the
                # full buffer.  The full operand size is kept in attrs so
                # engines on non-aliasing backends (XLA-CPU) can re-add the
                # copy cost (hw.scatter_inplace=False).
                operand_bytes = _aval_bytes(eqn.invars[0].aval)
                upd_bytes = sum(_aval_bytes(v.aval) for v in eqn.invars[1:]
                                if not isinstance(v, jex_core.Literal))
                common = dict(common, bytes_in=upd_bytes, bytes_out=upd_bytes)
                node = g.op(kind, **common)
                node.attrs["operand_bytes"] = operand_bytes
                for v in eqn.outvars:
                    ctx.producer[v] = node.name
                continue
            node = g.op(kind, **common)
        elif prim in TRANSCENDENTAL:
            node = g.op("elementwise", flops=4.0 * _aval_elems(out), **common)
        elif prim in ELEMENTWISE or out is not None:
            node = g.op("elementwise", flops=_aval_elems(out), **common)
        else:
            continue
        for v in eqn.outvars:
            ctx.producer[v] = node.name
    return ctx


def trace(fn: Callable, *args, name: str = "traced", phase: str = "fwd",
          coalesce: bool = True, **kwargs) -> Graph:
    """Native ingestion: any JAX callable + example (abstract) args -> Graph."""
    closed = jax.make_jaxpr(partial(fn, **kwargs) if kwargs else fn)(*args)
    g = Graph(name)
    ctx = _TraceCtx(g)
    _trace_jaxpr(ctx, closed.jaxpr, 1.0, phase)
    if coalesce:
        g = coalesce_elementwise(g)
    return g


def trace_grad(fn: Callable, *args, name: str = "joint", **kwargs) -> Graph:
    """Joint forward+backward graph via jax.vjp (aot_autograd analogue).
    Backward-only cost = joint - forward (paper partitions the joint graph)."""

    def joint(*a):
        out, vjp = jax.vjp(partial(fn, **kwargs) if kwargs else fn, *a)
        cts = jax.tree.map(jnp.ones_like, out)
        return vjp(cts)

    return trace(joint, *args, name=name, phase="bwd")


# --------------------------------------------------------------------------
# PyTorch-profiler granularity: coalesce adjacent elementwise chains
# --------------------------------------------------------------------------

def coalesce_elementwise(g: Graph) -> Graph:
    """Fuse elementwise/copy chains into single nodes (matching what XLA's
    fuser — and the paper's operator granularity — would show)."""
    FUSABLE = {"elementwise", "copy"}
    succ_n = {k: len(v) for k, v in g.successors().items()}
    out = Graph(g.name)
    alias: dict[str, str] = {}
    orig_of: dict[str, str] = {}  # output-graph name -> last original fused in
    for node in g.toposort():
        deps = [alias.get(d, d) for d in node.deps]
        if node.kind in FUSABLE and deps:
            cand = deps[0]
            if (cand in out.nodes and out.nodes[cand].kind in FUSABLE
                    and out.nodes[cand].repeat == node.repeat
                    and succ_n.get(orig_of.get(cand, cand), 2) == 1):
                p = out.nodes[cand]
                p.flops += node.flops
                p.bytes_out = node.bytes_out        # chain output replaces
                p.out_shape = node.out_shape or p.out_shape
                for d in deps[1:]:
                    if d != p.name and d not in p.deps:
                        p.deps.append(d)
                alias[node.name] = p.name
                orig_of[p.name] = node.name
                continue
        nn = node.clone()
        nn.deps = [d for d in dict.fromkeys(deps) if d != nn.name]
        out.nodes[nn.name] = nn
        orig_of[nn.name] = node.name
    return out
