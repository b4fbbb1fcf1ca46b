"""Model ingestion: ModelConfig -> per-block operator graphs (paper §3.2a).

Charon extracts and simulates a single transformer block per distinct block
kind and extrapolates over depth; asymmetric stacks (whisper enc/dec,
recurrentgemma hybrid cycle) trace each kind separately.  The block
functions are handed core/stubs.py's ``attention_stub`` as their attention,
so each attention is traced as a single abstract operator.

All graphs are traced at the *per-data-shard* batch (B_local); the
parallelism passes then rewrite for TP/SP/EP/CP.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import tracer
from repro.core.ir import Graph
from repro.core.stubs import attention_stub
from repro.models import abstract_params, block_cycle, lead_blocks
from repro.models.kvcache import build_cache
from repro.models.model import apply_block_decode, apply_block_full


@dataclass
class BlockGraphs:
    kind: str
    repeat: int                      # how many times this block occurs
    fwd: Graph
    joint: Graph | None = None       # fwd+bwd (train)


@dataclass
class ModelGraphs:
    cfg: ModelConfig
    mode: str
    blocks: list[BlockGraphs]
    head: BlockGraphs | None = None  # embed + final norm + logits (+ loss bwd)
    encoder: BlockGraphs | None = None

    def all_blocks(self):
        out = list(self.blocks)
        if self.encoder:
            out.append(self.encoder)
        if self.head:
            out.append(self.head)
        return out


def ingest_key(cfg: ModelConfig, B_local: int, S: int, mode: str,
               cache_len: int = 0) -> tuple:
    """Memoization key for :func:`block_graphs`.

    ``ModelConfig`` is a frozen dataclass of hashable fields, so the config
    itself is the model fingerprint.  Two calls with equal keys trace
    identical graphs; callers must clone before mutating (the simulator's
    pass pipeline already does)."""
    return (cfg, B_local, S, mode, cache_len)


# --------------------------------------------------------------------------
# Batch extrapolation: Charon's single-block trick applied to the batch axis.
#
# Within one ingest *family* (cfg, S, mode, cache_len) every traced quantity
# is affine in B_local: tensor shapes carry at most one batch factor, so
# every dim, byte count and FLOP count is a + c*B with non-negative dyadic
# coefficients.  Two anchor traces at batch b1, b2 (|b2-b1| a power of two,
# so the coefficient division is exact in binary floating point) determine
# the whole family; the first ``_VERIFY_POINTS`` non-anchor requests are
# still traced directly and compared field-by-field against the
# interpolation — only after those prove bit-exact does the family skip JAX
# tracing.  Any structural or numeric mismatch permanently disables
# extrapolation for the family (silent, correct fallback).
# --------------------------------------------------------------------------

_NODE_NUM_FIELDS = ("flops", "bytes_in", "bytes_out", "comm_bytes")
_NODE_CONST_FIELDS = ("name", "kind", "dtype", "comm_group", "comm_size",
                      "overlappable", "stream", "repeat", "phase")
_VERIFY_POINTS = 2
_FAMILY_MAX = 64                 # runaway backstop, not a tuning knob


@dataclass
class _Family:
    traced: dict = field(default_factory=dict)   # B -> ModelGraphs (direct)
    pair: tuple | None = None                    # anchor (b1, b2)
    verified: int = 0
    disabled: bool = False


_FAMILIES: dict = {}
_EXTRAP_STATS = {"extrapolated": 0, "traced": 0}


def ingest_extrapolation_stats() -> dict:
    return dict(_EXTRAP_STATS)


def ingest_extrapolation_clear() -> None:
    _FAMILIES.clear()
    _EXTRAP_STATS.update(extrapolated=0, traced=0)


def _affine(v1, v2, b1: int, b2: int, B: int):
    """Exact affine reconstruction v(B) from (b1, v1), (b2, v2); None if the
    fit is not an exact non-negative affine function."""
    if isinstance(v1, bool) or isinstance(v2, bool):
        return v1 if v1 == v2 else None
    if isinstance(v1, int) and isinstance(v2, int):
        d = b2 - b1
        if (v2 - v1) % d:
            return None
        c = (v2 - v1) // d
        a = v1 - c * b1
        if c < 0 or a < 0:
            return None
        return a + c * B
    if isinstance(v1, float) and isinstance(v2, float):
        # b2-b1 is a power of two and traced values are dyadic rationals
        # well inside the 53-bit mantissa: every step below is exact
        c = (v2 - v1) / (b2 - b1)
        a = v1 - c * b1
        if c < 0.0 or a < 0.0:
            return None
        return a + c * B
    return v1 if v1 == v2 else None


def _affine_seq(s1, s2, b1, b2, B):
    if len(s1) != len(s2):
        return None
    out = []
    for v1, v2 in zip(s1, s2):
        v = _affine(v1, v2, b1, b2, B)
        if v is None:
            return None
        out.append(v)
    return tuple(out)


def _interp_graph(g1: Graph, g2: Graph, b1: int, b2: int, B: int) -> Graph | None:
    if len(g1) != len(g2):
        return None
    out = Graph(g1.name)
    out._ctr = g1._ctr
    for n1, n2 in zip(g1.nodes.values(), g2.nodes.values()):
        for f in _NODE_CONST_FIELDS:
            if getattr(n1, f) != getattr(n2, f):
                return None
        if n1.deps != n2.deps:
            return None
        n = n1.clone()
        for f in _NODE_NUM_FIELDS:
            v = _affine(getattr(n1, f), getattr(n2, f), b1, b2, B)
            if v is None:
                return None
            setattr(n, f, v)
        shape = _affine_seq(n1.out_shape, n2.out_shape, b1, b2, B)
        if shape is None:
            return None
        n.out_shape = shape
        if set(n1.attrs) != set(n2.attrs):
            return None
        for k, v1 in n1.attrs.items():
            v2 = n2.attrs[k]
            if isinstance(v1, tuple) and isinstance(v2, tuple):
                v = _affine_seq(v1, v2, b1, b2, B)
            elif isinstance(v1, (int, float)) and isinstance(v2, (int, float)):
                v = _affine(v1, v2, b1, b2, B)
            else:
                v = v1 if v1 == v2 else None
            if v is None:
                return None
            n.attrs[k] = v
        out.nodes[n.name] = n
    return out


def _interp_block(bg1: BlockGraphs, bg2: BlockGraphs, b1, b2, B):
    if bg1.kind != bg2.kind or bg1.repeat != bg2.repeat \
            or (bg1.joint is None) != (bg2.joint is None):
        return None
    fwd = _interp_graph(bg1.fwd, bg2.fwd, b1, b2, B)
    if fwd is None:
        return None
    joint = None
    if bg1.joint is not None:
        joint = _interp_graph(bg1.joint, bg2.joint, b1, b2, B)
        if joint is None:
            return None
    return BlockGraphs(bg1.kind, bg1.repeat, fwd, joint)


def _interp_model(mg1: ModelGraphs, mg2: ModelGraphs, b1, b2, B):
    if len(mg1.blocks) != len(mg2.blocks) \
            or (mg1.head is None) != (mg2.head is None) \
            or (mg1.encoder is None) != (mg2.encoder is None):
        return None
    blocks = []
    for bg1, bg2 in zip(mg1.blocks, mg2.blocks):
        bg = _interp_block(bg1, bg2, b1, b2, B)
        if bg is None:
            return None
        blocks.append(bg)
    head = encoder = None
    if mg1.head is not None:
        head = _interp_block(mg1.head, mg2.head, b1, b2, B)
        if head is None:
            return None
    if mg1.encoder is not None:
        encoder = _interp_block(mg1.encoder, mg2.encoder, b1, b2, B)
        if encoder is None:
            return None
    return ModelGraphs(mg1.cfg, mg1.mode, blocks, head, encoder)


def _graphs_match(a: ModelGraphs, b: ModelGraphs) -> bool:
    def sig(mg):
        out = []
        for bg in mg.all_blocks():
            for g in (bg.fwd, bg.joint):
                if g is None:
                    continue
                out.append((bg.kind, bg.repeat,
                            [(n.name, n.kind, n.dtype, n.flops, n.bytes_in,
                              n.bytes_out, n.comm_bytes, n.comm_group,
                              n.comm_size, n.overlappable, n.stream,
                              n.repeat, n.phase, tuple(n.out_shape),
                              tuple(sorted(n.attrs.items())), tuple(n.deps))
                             for n in g.nodes.values()]))
        return out
    return sig(a) == sig(b)


def ingest_graphs(cfg: ModelConfig, B_local: int, S: int, mode: str,
                  *, cache_len: int = 0) -> ModelGraphs:
    """:func:`block_graphs` with verified batch extrapolation (the
    simulator's ingest builder).  Callers must treat results as immutable —
    the same contract the per-simulator ingest cache already imposes."""
    key = (cfg, S, mode, cache_len)
    fam = _FAMILIES.get(key)
    if fam is None:
        if len(_FAMILIES) >= _FAMILY_MAX:
            _FAMILIES.clear()
        fam = _FAMILIES[key] = _Family()
    mg = fam.traced.get(B_local)
    if mg is not None:
        return mg
    interp = None
    # B_local == 1 is never anchored or interpolated: degenerate batch dims
    # genuinely change trace structure (e.g. the train head's loss backward
    # collapses its batch reduction), so batch 1 always traces directly
    if B_local > 1 and not fam.disabled and fam.pair is not None:
        b1, b2 = fam.pair
        interp = _interp_model(fam.traced[b1], fam.traced[b2], b1, b2, B_local)
        if interp is None:
            fam.disabled = True
        elif fam.verified >= _VERIFY_POINTS:
            _EXTRAP_STATS["extrapolated"] += 1
            return interp
    _EXTRAP_STATS["traced"] += 1
    mg = block_graphs(cfg, B_local, S, mode, cache_len=cache_len)
    if not fam.disabled:
        if interp is not None:
            if _graphs_match(interp, mg):
                fam.verified += 1
            else:
                fam.disabled = True
        elif fam.pair is None and B_local > 1:
            for b in sorted(fam.traced):
                d = B_local - b
                if b > 1 and d > 0 and (d & (d - 1)) == 0:  # 2^k spacing
                    fam.pair = (b, B_local)
                    break
        fam.traced[B_local] = mg
    return mg


def _layer_slice(tree: dict, group: str, pos: int):
    """One layer at ``pos`` of a ``blocks`` group of an abstract params or
    cache tree; ``cycle`` leaves drop their stacked layer dim."""
    one = tree["blocks"][group][pos]
    if group != "cycle":
        return one
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), one)


def _tag_moe(g: Graph, cfg: ModelConfig) -> Graph:
    """Mark the experts' grouped matmuls (one row per routed pair) for
    expert parallelism."""
    if cfg.num_experts:
        for n in g:
            if n.kind == "matmul" and n.attrs.get("ragged"):
                n.attrs["moe_expert"] = True
    return g


def block_graphs(cfg: ModelConfig, B_local: int, S: int, mode: str,
                 *, cache_len: int = 0) -> ModelGraphs:
    """Trace one graph per distinct block kind (+ embed/head)."""
    cycle, n_cycles, tail = block_cycle(cfg)
    # (kind, params group, position, layers of it): the lead, then the cycle
    # with the tail counted in
    layers = [(k, "lead", j, 1) for j, k in enumerate(lead_blocks(cfg))]
    layers += [(k, "cycle", j, n_cycles + (1 if j < len(tail) else 0))
               for j, k in enumerate(cycle)]
    dt = jnp.dtype(cfg.dtype)
    D = cfg.d_model
    x_abs = jax.ShapeDtypeStruct((B_local, S, D), dt)
    pos_abs = jax.ShapeDtypeStruct((B_local, S, 3) if cfg.rope_style == "mrope"
                                   else (B_local, S), jnp.int32)
    enc_abs = jax.ShapeDtypeStruct((B_local, cfg.encoder_seq, D), dt) \
        if cfg.cross_attention else None

    blocks: list[BlockGraphs] = []
    seen_kinds: dict[str, BlockGraphs] = {}
    for kind, group, j, repeat in layers:
        if kind in seen_kinds:
            seen_kinds[kind].repeat += repeat
            continue
        p_abs = _layer_slice(abstract_params(cfg), group, j)
        if mode == "decode":
            cache_abs = _layer_slice(build_cache(
                cfg, lambda s, l, d: jax.ShapeDtypeStruct(s, d), B_local,
                cache_len or S), group, j)
            x1 = jax.ShapeDtypeStruct((B_local, 1, D), dt)
            posv = jax.ShapeDtypeStruct((B_local,), jnp.int32)

            def dec_fn(p, x, cache, pos, _kind=kind):
                aux = {"pos": pos, "decode_positions": pos[:, None]}
                h, c = apply_block_decode(cfg, _kind, p, x, cache, aux,
                                          attention=attention_stub)
                return h

            fwd = _tag_moe(tracer.trace(dec_fn, p_abs, x1, cache_abs, posv,
                                        name=f"{kind}.decode"), cfg)
            bg = BlockGraphs(kind, repeat, fwd)
        else:
            def fwd_fn(p, x, positions, enc=None, _kind=kind):
                aux = {"positions": positions, "cache_len": 0}
                if enc is not None:
                    aux["enc_out"] = enc
                h, _, aux_l = apply_block_full(cfg, _kind, p, x, aux, False,
                                               attention=attention_stub)
                return h if mode != "train" else (h, aux_l)

            args = (p_abs, x_abs, pos_abs) + ((enc_abs,) if enc_abs is not None else ())
            if mode == "train":
                fwd = _tag_moe(tracer.trace(
                    lambda *a: fwd_fn(*a)[0], *args, name=f"{kind}.fwd"), cfg)
                joint = _tag_moe(tracer.trace_grad(
                    lambda *a: fwd_fn(*a)[0], *args, name=f"{kind}.joint"), cfg)
                bg = BlockGraphs(kind, repeat, fwd, joint)
            else:
                fwd = _tag_moe(tracer.trace(fwd_fn, *args, name=f"{kind}.fwd"), cfg)
                bg = BlockGraphs(kind, repeat, fwd)
        seen_kinds[kind] = bg
        blocks.append(bg)

    # encoder (whisper)
    encoder = None
    if cfg.encoder_layers > 0 and mode != "decode":
        p_enc = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
            abstract_params(cfg)["encoder"]["blocks"]["cycle"][0])
        xe = jax.ShapeDtypeStruct((B_local, cfg.encoder_seq, D), dt)
        pe = jax.ShapeDtypeStruct((B_local, cfg.encoder_seq), jnp.int32)

        def enc_fn(p, x, positions):
            h, _, _ = apply_block_full(cfg, "enc", p, x, {"positions": positions},
                                       False, attention=attention_stub)
            return h

        efwd = tracer.trace(enc_fn, p_enc, xe, pe, name="enc.fwd")
        ejoint = tracer.trace_grad(enc_fn, p_enc, xe, pe, name="enc.joint") \
            if mode == "train" else None
        encoder = BlockGraphs("enc", cfg.encoder_layers, efwd, ejoint)

    # embed + head (+ CE loss for train)
    S_head = 1 if mode == "decode" else S
    tok_abs = jax.ShapeDtypeStruct((B_local, S_head), jnp.int32)
    emb_abs = jax.ShapeDtypeStruct((cfg.vocab_size, D), jnp.dtype(cfg.param_dtype))
    nrm_abs = {"w": jax.ShapeDtypeStruct((D,), jnp.dtype(cfg.param_dtype))}
    if cfg.norm == "layernorm":
        nrm_abs["b"] = jax.ShapeDtypeStruct((D,), jnp.dtype(cfg.param_dtype))
    h_abs = jax.ShapeDtypeStruct((B_local, S_head, D), dt)

    def head_fn(emb_w, nrm, h, tokens):
        from repro.models import layers as L
        x = jnp.take(emb_w, tokens, axis=0).astype(dt)
        hh = h + x * 0  # keep both paths alive
        hh = L.apply_norm(cfg, nrm, hh)
        logits = jnp.einsum("bsd,dv->bsv", hh, emb_w.T.astype(dt)).astype(jnp.float32)
        if mode == "train":
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, jnp.maximum(tokens, 0)[..., None], axis=-1))
        return logits

    hf = tracer.trace(head_fn, emb_abs, nrm_abs, h_abs, tok_abs, name="head.fwd")
    hj = tracer.trace_grad(head_fn, emb_abs, nrm_abs, h_abs, tok_abs,
                           name="head.joint") if mode == "train" else None
    head = BlockGraphs("head", 1, hf, hj)

    return ModelGraphs(cfg, mode, blocks, head, encoder)
