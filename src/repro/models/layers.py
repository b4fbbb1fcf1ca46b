"""Core neural layers for the architecture zoo, in pure JAX.

Everything is functional: ``apply(params, x, ...) -> y``.  Layers insert
logical sharding constraints via :mod:`repro.distributed.sharding` so the same
code lowers correctly on 1 CPU device, a 16x16 pod, or the 2x16x16 multi-pod
mesh.

Every attention goes through :func:`attention`, which alone chooses how it
runs (:func:`attention_plan`): plain einsum softmax (``dense``), lax.scan
online softmax (``blockwise``, memory-safe at 32k+), or the Pallas flash
kernel (``flash``, one TPU, a forward that is not differentiated).
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import active_env, axis_size, logical_constraint as shard


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm(w: jax.Array, x: jax.Array, *, eps: float = 1e-6, offset: bool = False) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if offset else w.astype(jnp.float32)
    return (y * scale).astype(dt)


def layernorm(w: jax.Array, b: jax.Array, x: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def apply_norm(cfg, p: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(p["w"], p["b"], x, eps=cfg.norm_eps)
    return rmsnorm(p["w"], x, eps=cfg.norm_eps, offset=cfg.rms_offset)


# --------------------------------------------------------------------------
# Rotary position embeddings (standard / partial / M-RoPE)
# --------------------------------------------------------------------------

def _rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's magnitude correction, 0.1 * mscale * ln(scale) + 1 (1 at
    scale <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_freqs(cfg, dim: int) -> jax.Array:
    """YaRN inverse frequencies (DeepSeek-V3's ``rope_scaling`` "yarn"):
    pairs that turn more than ``beta_fast`` times over the original context
    keep their frequency, those that turn fewer than ``beta_slow`` times are
    divided by ``factor``, with a linear ramp between.  YaRN's factor on cos
    and sin, mscale(factor, mscale) / mscale(factor, mscale_all_dim), is 1
    where the two mscales are equal, as DeepSeek-V3 publishes them."""
    base, factor, orig = cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_max_pos

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    extra = _rope_freqs(dim, base)
    return extra / factor * ramp + extra * (1.0 - ramp)


def attn_scale(cfg) -> float:
    """Factor on the softmax scale: YaRN's mscale(factor, mscale_all_dim)^2,
    1 without YaRN."""
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return 1.0


def _rotate(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: (..., D_rot) with angles (..., D_rot/2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(cfg, x: jax.Array, positions: jax.Array) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32 or (B, S, 3) for M-RoPE."""
    if cfg.rope_style == "none":
        return x
    d = x.shape[-1]
    rot = d if cfg.rope_style != "partial" else int(d * cfg.rope_fraction)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    inv = _yarn_freqs(cfg, rot) if cfg.yarn_factor else _rope_freqs(rot, cfg.rope_theta)
    if cfg.rope_style == "mrope":
        # 3-section rotary (t, h, w): split the half-dim 1/4, 3/8, 3/8
        # (Qwen2-VL mrope_section, e.g. [16, 24, 24] for half=64).
        if positions.ndim == 2:
            positions = jnp.broadcast_to(positions[..., None], (*positions.shape, 3))
        s0 = half // 4
        s1 = s0 + (3 * half) // 8
        sec = jnp.concatenate([
            jnp.zeros((s0,), jnp.int32),
            jnp.ones((s1 - s0,), jnp.int32),
            jnp.full((half - s1,), 2, jnp.int32),
        ])
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),              # (B, S, 3)
            jnp.broadcast_to(sec, (*positions.shape[:2], half)), axis=-1)
    else:
        pos = positions.astype(jnp.float32)[..., None]  # (B, S, 1)
        pos = jnp.broadcast_to(pos, (*positions.shape, half))
    angles = pos[..., None, :] * inv                     # (B, S, 1, half)
    out = _rotate(x_rot, angles)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1) if rot < d else out.astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d_model: int) -> jax.Array:
    """Whisper-style sinusoidal embedding; positions (B, S) -> (B, S, D)."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(10_000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# Softmax attention (dense / blockwise) over GQA layouts
# --------------------------------------------------------------------------

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _soft_cap(s: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(s / cap) * cap if cap > 0 else s


def attend_dense(q, k, v, *, q_offset, causal: bool, window: int = 0,
                 kv_valid_len=None, soft_cap: float = 0.0, scale: float | None = None):
    """q: (B, Sq, Hkv, G, Dq), k: (B, T, Hkv, Dq), v: (B, T, Hkv, Dv).

    ``q_offset``: absolute position of q[0] (decode: cache length written so far).
    ``kv_valid_len``: scalar or (B,) — entries >= this in T are masked (ring caches).
    """
    B, Sq, Hkv, G, Dq = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dq)
    s = jnp.einsum("bskgd,btkd->bkgst", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = _soft_cap(s, soft_cap)
    q_pos = q_offset + jnp.arange(Sq)
    t_pos = jnp.arange(T)
    mask = jnp.ones((Sq, T), bool)
    if causal:
        mask &= t_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= t_pos[None, :] > q_pos[:, None] - window
    mask = jnp.broadcast_to(mask, (B, 1, 1, Sq, T))
    if kv_valid_len is not None:
        vl = jnp.asarray(kv_valid_len)
        vl = vl.reshape(-1, 1, 1, 1, 1) if vl.ndim else vl
        mask = mask & (t_pos[None, None, None, None, :] < vl)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def attend_blockwise(q, k, v, *, q_offset, causal: bool, window: int = 0,
                     kv_valid_len=None, soft_cap: float = 0.0,
                     q_block: int, kv_block: int, scale: float | None = None):
    """Online-softmax (flash-style) attention in pure JAX, in float32.

    Outer Python loop over q blocks (static trip count) so causal and
    windowed runs can statically skip the kv blocks a q block never sees;
    inner ``lax.scan`` over kv blocks carries the running (m, l, acc).
    """
    B, Sq, Hkv, G, Dq = q.shape
    T, Dv = k.shape[1], v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dq)
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, T)
    # pad to block multiples
    Sq_p = -(-Sq // q_block) * q_block
    T_p = -(-T // kv_block) * kv_block
    qp = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, T_p - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, T_p - T), (0, 0), (0, 0)))
    n_kv = T_p // kv_block

    if kv_valid_len is not None:
        vl = jnp.asarray(kv_valid_len)
        vl_b = vl.reshape(-1, 1, 1, 1, 1) if vl.ndim else vl
    outs = []
    for qi in range(Sq_p // q_block):
        q_blk = qp[:, qi * q_block:(qi + 1) * q_block].astype(jnp.float32)
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)
        # static causal truncation: kv blocks strictly after this q block's
        # last row are fully masked -> skip (saves ~2x flops at scale)
        hi = n_kv
        if causal and isinstance(q_offset, int):
            last = q_offset + (qi + 1) * q_block - 1
            hi = min(n_kv, last // kv_block + 1)
        lo = 0
        if window > 0 and isinstance(q_offset, int):
            first = max(q_offset + qi * q_block - window + 1, 0)
            lo = min(first // kv_block, hi)

        def step(carry, ti):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(kp, ti * kv_block, kv_block, 1).astype(jnp.float32)
            vb = jax.lax.dynamic_slice_in_dim(vp, ti * kv_block, kv_block, 1).astype(jnp.float32)
            s = jnp.einsum("bskgd,btkd->bkgst", q_blk, kb) * scale
            s = _soft_cap(s, soft_cap)
            t_pos = ti * kv_block + jnp.arange(kv_block)
            msk = t_pos[None, :] < T  # padding
            if causal:
                msk &= t_pos[None, :] <= q_pos[:, None]
            if window > 0:
                msk &= t_pos[None, :] > q_pos[:, None] - window
            msk = jnp.broadcast_to(msk, (B, 1, 1, q_block, kv_block))
            if kv_valid_len is not None:
                msk = msk & (t_pos[None, None, None, None, :] < vl_b)
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bkgst,btkd->bkgsd", p, vb)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_block, Dv), jnp.float32)
        if hi > lo:
            (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(lo, hi))
        else:
            m, l, acc = m0, l0, a0
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(jnp.einsum("bkgsd->bskgd", o))
    o = jnp.concatenate(outs, axis=1)[:, :Sq]
    return o.astype(q.dtype)


BLOCK_Q, BLOCK_KV = 2048, 512      # blockwise attention's tiles
FLASH_Q, FLASH_KV = 512, 512       # the flash kernel's tiles


def attention_plan(sq: int, t: int, heads: int, *, flash: bool, backend: str,
                   model_axis: int | None) -> tuple[str, int | None, int | None]:
    """How :func:`attention` runs: ``(implementation, q tile, kv tile)``.

    ``sq``/``t``: query and key lengths; ``heads``: the query's third axis
    (kv heads for GQA, every head for MLA); ``flash``: the caller allows
    the flash kernel and the call meets its preconditions; ``backend``:
    ``jax.default_backend()``; ``model_axis``: the size of the sharding
    environment's ``model`` axis, None without one.

    The flash kernel runs on one TPU with no sharding environment (it has
    no VJP and no partitioning rule).  A single query row, or a score
    matrix of at most 2048^2 with at most 1024 keys, attends densely;
    anything larger is blockwise.  Where the ``model`` axis does not divide
    the heads, self-attention's queries are split over it by sequence
    (context parallelism), so one q block spans them all: no q block
    crosses a shard boundary.
    """
    if flash and backend == "tpu" and model_axis is None:
        return "flash", FLASH_Q, FLASH_KV
    if sq == 1 or (sq * t <= 2048 * 2048 and t <= 1024):
        return "dense", None, None
    q_block = sq if model_axis and heads % model_axis and sq == t else BLOCK_Q
    return "blockwise", q_block, BLOCK_KV


def attention(q, k, v, *, q_offset=0, causal=True, window=0, kv_valid_len=None,
              soft_cap=0.0, scale=None, flash=False):
    """Softmax attention, shapes as in :func:`attend_dense`, run as
    :func:`attention_plan` chooses.  ``flash``: the caller's forward will
    not be differentiated, so the flash kernel may take it where it runs
    (no query offset, valid length or soft cap)."""
    env = active_env()
    impl, q_block, kv_block = attention_plan(
        q.shape[1], k.shape[1], q.shape[2],
        flash=flash and q_offset == 0 and kv_valid_len is None and not soft_cap,
        backend=jax.default_backend(),
        model_axis=None if env is None else axis_size("model", env))
    if impl == "flash":
        from repro.kernels import ops
        return ops.flash_attention_bshd(q, k, v, causal=causal, window=window, scale=scale,
                                        block_q=q_block, block_k=kv_block)
    if impl == "blockwise":
        return attend_blockwise(q, k, v, q_offset=q_offset, causal=causal, window=window,
                                kv_valid_len=kv_valid_len, soft_cap=soft_cap, scale=scale,
                                q_block=q_block, kv_block=kv_block)
    return attend_dense(q, k, v, q_offset=q_offset, causal=causal, window=window,
                        kv_valid_len=kv_valid_len, soft_cap=soft_cap, scale=scale)


# --------------------------------------------------------------------------
# Dense projections / FFN
# --------------------------------------------------------------------------

def linear(p: dict, x: jax.Array) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, p["w"].astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def named_scope(scope: str | None = None):
    """Decorator: run the function under ``jax.named_scope(scope)``, so its
    ops carry ``/<scope>/`` in their HLO ``op_name`` and a device op in a
    profile can be put under attention, the FFN or the head; the math is
    unchanged.  Without ``scope``, the block kind (the function's second
    argument) names it."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(scope or args[1]):
                return fn(*args, **kwargs)
        return scoped
    return wrap


@named_scope("ffn")
def ffn(cfg, p: dict, x: jax.Array) -> jax.Array:
    """SwiGLU / GeGLU / plain-GELU feed-forward."""
    if cfg.act in ("swiglu", "geglu"):
        g = linear(p["gate"], x)
        u = linear(p["up"], x)
        g = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g, approximate=True)
        h = g * u
    else:
        h = jax.nn.gelu(linear(p["up"], x), approximate=True)
    h = shard(h, ("batch", "seq", "ffn"))
    return linear(p["down"], h)


# --------------------------------------------------------------------------
# Mixture of Experts (dropless; this device's share of the experts)
# --------------------------------------------------------------------------

def _route(cfg, xf: jax.Array, router: dict):
    """Top-k routing over all ``num_experts`` experts.  xf: (T, D) ->
    expert ids (T, K), their weights (T, K) in float32, and the Switch
    load-balancing aux loss (0 for noaux_tc, which balances by its bias).

    ``softmax``: top-k of the softmax.  ``noaux_tc`` (DeepSeek-V3): sigmoid
    scores; the correction bias is added for the choice only; each group of
    experts is scored by the sum of its two best, the tokens keep the
    ``topk_group`` best groups and take their top-k there; the weights are
    the chosen sigmoid scores.  Both normalise the k weights to sum to 1
    and scale them by ``routed_scaling_factor``.
    """
    T = xf.shape[0]
    E, K = cfg.num_experts, cfg.top_k
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router["w"].astype(jnp.float32))
    aux = jnp.zeros((), jnp.float32)
    if cfg.router == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, ids = jax.lax.top_k(probs, K)                     # (T, K)
        onehot = jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32)
        aux = E * jnp.mean(jnp.mean(onehot, 0) * jnp.mean(probs, 0)) * cfg.router_aux_coef
    elif cfg.router == "noaux_tc":
        scores = jax.nn.sigmoid(logits)
        pick = scores + router["bias"].astype(jnp.float32)
        if cfg.n_group > 1:
            G = cfg.n_group
            grouped = pick.reshape(T, G, E // G)
            group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)              # (T, G)
            top_groups = jax.lax.top_k(group_score, cfg.topk_group)[1]
            keep = jax.nn.one_hot(top_groups, G, dtype=jnp.float32).sum(1) > 0
            pick = jnp.where(jnp.repeat(keep, E // G, axis=1), pick, -jnp.inf)
        ids = jax.lax.top_k(pick, K)[1]
        gate_vals = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        raise ValueError(f"unknown router {cfg.router!r}")
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return ids, gate_vals * cfg.routed_scaling_factor, aux


def _held_experts(xf: jax.Array, ids: jax.Array, gate_vals: jax.Array, w: dict,
                  offset) -> jax.Array:
    """The part of the experts in ``w`` (E of them, ids from ``offset``) on
    xf (T, D), dropless: the (token, expert) pairs are sorted by expert, the
    held experts' pairs first, and one ragged product per matrix gives each
    group of rows its expert.  Pairs of experts not held fall past the
    groups and add nothing.  So the matmuls have ``T * top_k`` rows, one per
    routed pair.  Returns (T, D) float32."""
    T, D = xf.shape
    K, E = ids.shape[1], w["gate"].shape[0]
    dt = xf.dtype
    with jax.named_scope("moe_route"):
        local = ids.reshape(-1) - offset                              # (T*K,)
        local = jnp.where((local >= 0) & (local < E), local, E)      # E: not held
        order = jnp.argsort(local, stable=True)
        sizes = jnp.bincount(local, length=E + 1)[:E].astype(jnp.int32)
        xs = xf[order // K]
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(xs, w["gate"].astype(dt), sizes)
        u = jax.lax.ragged_dot(xs, w["up"].astype(dt), sizes)
        eo = jax.lax.ragged_dot(jax.nn.silu(g) * u, w["down"].astype(dt), sizes)
    with jax.named_scope("moe_route"):
        eo = jnp.where((local[order] < E)[:, None], eo, 0)
        back = jnp.zeros_like(eo).at[order].set(eo).reshape(T, K, D)
        return (back.astype(jnp.float32) * gate_vals[..., None]).sum(1)


@named_scope("moe")
def moe_ffn(cfg, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Routed experts, dropless, over this device's share of them, plus the
    shared expert.

    The router scores all ``num_experts`` experts and picks each token's
    top-k among them; this device adds ``weight * expert(x)`` for the
    chosen experts it holds (``held_experts`` from ``expert_offset``) and
    leaves out what absent experts would add.  No token is dropped: every
    routed pair to a held expert is computed, and only those
    (:func:`_held_experts`).

    On a mesh the held experts are sharded over its ``model`` axis under
    ``shard_map`` (GSPMD cannot shard the sort and gather): where the tokens
    are sharded over that axis too, each device gathers them all, computes
    its experts' pairs and a reduce-scatter sums the parts back onto the
    tokens' owners; where they are not, a ``psum`` sums the parts.  The
    buffers stay at a row per routed pair, whatever the routing.

    Scopes: ``moe_route`` (router, group top-k, sort, unsort and combine),
    ``moe_experts`` (the held experts' matmuls); the shared expert is
    ``ffn``.  Returns (output, router_aux_loss).
    """
    from repro.distributed.sharding import active_env, resolve_spec

    B, S, D = x.shape
    env = active_env()
    mesh = env.mesh if env is not None else None
    m = mesh.shape.get("model", 1) if mesh is not None else 1
    if cfg.held_experts % m != 0:
        m = 1  # experts unshardable -> local compute, replicated weights

    def part(xf, router, experts, offset):
        with jax.named_scope("moe_route"):
            ids, gate_vals, aux = _route(cfg, xf, router)
        return _held_experts(xf, ids, gate_vals, experts, offset), aux

    if mesh is None or all(s == 1 for s in mesh.shape.values()):
        out, aux = part(x.reshape(B * S, D), p["router"], p["experts"], cfg.expert_offset)
        out = out.astype(x.dtype).reshape(B, S, D)
    else:
        from jax import shard_map
        P = jax.sharding.PartitionSpec
        x_spec = resolve_spec(env, ("batch", "seq_sp", None), x.shape)
        ew_spec = resolve_spec(env, ("expert", None, None), p["experts"]["gate"].shape)
        rep = jax.tree.map(lambda _: P(), p["router"])
        all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
        gather = m > 1 and "model" in jax.tree.leaves(tuple(x_spec))

        def body(x_loc, router, experts):
            b, s, _ = x_loc.shape
            xf = x_loc.reshape(b * s, D)
            offset = cfg.expert_offset
            if m > 1:
                offset = offset + jax.lax.axis_index("model") * (cfg.held_experts // m)
            with jax.named_scope("moe_route"):
                if gather:
                    xf = jax.lax.all_gather(xf, "model", tiled=True)
            out, aux = part(xf, router, experts, offset)
            with jax.named_scope("moe_route"):
                if gather:
                    out = jax.lax.psum_scatter(out, "model", tiled=True)
                elif m > 1:
                    out = jax.lax.psum(out, "model")
            aux = jax.lax.pmean(aux, all_axes)
            return out.astype(x_loc.dtype).reshape(b, s, D), aux

        out, aux = shard_map(
            body, mesh=mesh,
            in_specs=(x_spec, rep, jax.tree.map(lambda _: ew_spec, p["experts"])),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(x, p["router"], p["experts"])

    if cfg.num_shared_experts > 0:
        out = out + ffn(cfg, p["shared"], x)
    return out, aux


# --------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma recurrent block)
# --------------------------------------------------------------------------

_RGLRU_C = 8.0


def _rglru_gate_matmul(w: jax.Array, x: jax.Array) -> jax.Array:
    """Full (W,W) or block-diagonal (nb,Wb,Wb) gate projection of (..., W)."""
    wf = w.astype(jnp.float32)
    if w.ndim == 3:
        nb, Wb, _ = w.shape
        xs = x.reshape(*x.shape[:-1], nb, Wb)
        xs = shard(xs, tuple([None] * (x.ndim - 1)) + ("lru_width", None))
        y = jnp.einsum("...nw,nwv->...nv", xs, wf)
        return y.reshape(*x.shape)
    return jnp.einsum("...w,wv->...v", x, wf)


def rglru_scan(p: dict, x: jax.Array, h0: jax.Array | None):
    """x: (B, S, W).  Returns (y, h_last).  Diagonal gated linear recurrence:
    a_t = exp(-c softplus(L) * r_t);  h_t = a_t h_{t-1} + sqrt(1-a_t^2) i_t x_t.
    """
    B, S, W = x.shape
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(_rglru_gate_matmul(p["wa"], xf) + p["ba"])
    i = jax.nn.sigmoid(_rglru_gate_matmul(p["wx"], xf) + p["bx"])
    log_a = -_RGLRU_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r   # (B,S,W) <= 0
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = beta * i * xf
    if h0 is not None:
        # fold initial state into the first step: h_1 = a_1 h_0 + b_1
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    a_s, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(x.dtype), h[:, -1]


def rglru_step(p: dict, x_t: jax.Array, h: jax.Array):
    """Single decode step; x_t, h: (B, W)."""
    xf = x_t.astype(jnp.float32)
    r = jax.nn.sigmoid(_rglru_gate_matmul(p["wa"], xf) + p["ba"])
    i = jax.nn.sigmoid(_rglru_gate_matmul(p["wx"], xf) + p["bx"])
    log_a = -_RGLRU_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    h_new = a * h.astype(jnp.float32) + beta * i * xf
    return h_new.astype(x_t.dtype), h_new


def causal_conv1d(p: dict, x: jax.Array, state: jax.Array | None):
    """Depthwise causal conv (width K).  x: (B,S,W); state: (B,K-1,W) or None.
    Returns (y, new_state)."""
    Kw = p["w"].shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], Kw - 1, x.shape[2]), x.dtype)
    xx = jnp.concatenate([state, x], axis=1)
    y = sum(xx[:, i:i + x.shape[1]] * p["w"][i].astype(x.dtype) for i in range(Kw))
    y = y + p["b"].astype(x.dtype)
    return y, xx[:, -(Kw - 1):] if Kw > 1 else jnp.zeros((x.shape[0], 0, x.shape[2]), x.dtype)


# --------------------------------------------------------------------------
# xLSTM cells (mLSTM chunkwise-parallel + sLSTM sequential)
# --------------------------------------------------------------------------

def mlstm_chunkwise(q, k, v, i_gate, f_gate, state=None, *, chunk: int = 256):
    """Stabilised chunkwise mLSTM (matrix-memory) forward.

    q,k,v: (B, S, H, D);  i_gate,f_gate: (B, S, H) pre-activation.
    state: optional (C, n, m) with C:(B,H,D,D), n:(B,H,D), m:(B,H).
    Returns (y, (C,n,m)).  [arXiv:2405.04517], chunkwise form following
    flash-linear-attention GLA-style scan.
    """
    B, S, H, D = q.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        z3 = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        z2 = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        q, k, v = z3(q), z3(k), z3(v)
        i_gate, f_gate = z2(i_gate), z2(f_gate) - 1e9 * (jnp.arange(S + pad) >= S)[None, :, None]
    Sp = q.shape[1]
    NC = Sp // chunk
    shp = lambda t: t.reshape(B, NC, chunk, H, -1).astype(jnp.float32)
    q_, k_, v_ = shp(q), shp(k), shp(v)
    ig = i_gate.reshape(B, NC, chunk, H).astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_gate.reshape(B, NC, chunk, H).astype(jnp.float32))
    csum_f = jnp.cumsum(lf, axis=2)                    # within-chunk cumulative log-forget
    total_f = csum_f[:, :, -1]                         # (B, NC, H)

    scale = 1.0 / math.sqrt(D)
    if state is None:
        C0 = jnp.zeros((B, H, D, D), jnp.float32)
        n0 = jnp.zeros((B, H, D), jnp.float32)
        m0 = jnp.full((B, H), NEG_INF, jnp.float32)
    else:
        C0, n0, m0 = (s.astype(jnp.float32) for s in state)

    # intra-chunk decay matrix: dm[t, s] = csum_f[t] - csum_f[s] + ig[s] for s <= t
    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]

    def chunk_step(carry, inp):
        C, n, m = carry
        qc, kc, vc, igc, cfc, tfc = inp                # (B,chunk,H,*) ...
        # log weights for inter-chunk (state) and intra-chunk paths
        b_state = cfc                                  # (B,chunk,H): decay from chunk start
        g = cfc[:, :, None, :] - cfc[:, None, :, :] + igc[:, None, :, :]  # (B,t,s,H)
        g = jnp.where(causal[None, :, :, None], g, NEG_INF)
        m_intra = g.max(2)                                             # (B,chunk,H)
        m_t = jnp.maximum(b_state + m[:, None, :], m_intra)            # (B,chunk,H)
        w_state = jnp.exp(b_state + m[:, None, :] - m_t)               # (B,chunk,H)
        w_intra = jnp.exp(g - m_t[:, :, None, :])                      # (B,t,s,H)

        s_intra = jnp.einsum("bthd,bshd->btsh", qc, kc) * scale        # (B,t,s,H)
        num = jnp.einsum("btsh,btsh,bshd->bthd", s_intra, w_intra, vc) \
            + jnp.einsum("bthd,bhdk,bth->bthk", qc * scale, C, w_state)
        den = jnp.abs(jnp.einsum("btsh,btsh->bth", s_intra, w_intra)
                      + jnp.einsum("bthd,bhd,bth->bth", qc * scale, n, w_state))
        y = num / jnp.maximum(den, jnp.exp(-m_t))[..., None]           # lower-bound denom (xLSTM eq. 25)

        # state update to end of chunk
        m_next = jnp.maximum(tfc + m, (tfc[:, None, :] - cfc + igc).max(1))
        w_old = jnp.exp(tfc + m - m_next)                              # (B,H)
        kw = jnp.exp(tfc[:, None, :] - cfc + igc - m_next[:, None, :]) # (B,s,H)
        C_next = C * w_old[:, :, None, None] + jnp.einsum("bshd,bsh,bshk->bhdk", kc, kw, vc)
        n_next = n * w_old[:, :, None] + jnp.einsum("bshd,bsh->bhd", kc, kw)
        return (C_next, n_next, m_next), y

    inputs = (q_.transpose(1, 0, 2, 3, 4), k_.transpose(1, 0, 2, 3, 4),
              v_.transpose(1, 0, 2, 3, 4), ig.transpose(1, 0, 2, 3),
              csum_f.transpose(1, 0, 2, 3), total_f.transpose(1, 0, 2))
    (C, n, m), ys = jax.lax.scan(chunk_step, (C0, n0, m0), inputs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, Sp, H, D)[:, :S]
    return y.astype(q.dtype), (C, n, m)


def mlstm_step(q_t, k_t, v_t, i_t, f_t, state):
    """Single-token mLSTM update; q_t,k_t,v_t: (B,H,D); i_t,f_t: (B,H)."""
    C, n, m = state
    D = q_t.shape[-1]
    qf, kf, vf = (t.astype(jnp.float32) for t in (q_t, k_t, v_t))
    i_f = i_t.astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_t.astype(jnp.float32))
    m_new = jnp.maximum(lf + m, i_f)
    C = C * jnp.exp(lf + m - m_new)[..., None, None] + \
        jnp.exp(i_f - m_new)[..., None, None] * jnp.einsum("bhd,bhk->bhdk", kf, vf)
    n = n * jnp.exp(lf + m - m_new)[..., None] + jnp.exp(i_f - m_new)[..., None] * kf
    scale = 1.0 / math.sqrt(D)
    num = jnp.einsum("bhd,bhdk->bhk", qf * scale, C)
    den = jnp.abs(jnp.einsum("bhd,bhd->bh", qf * scale, n))
    y = num / jnp.maximum(den, jnp.exp(-m_new))[..., None]
    return y.astype(q_t.dtype), (C, n, m_new)


def slstm_scan(p: dict, x: jax.Array, state=None):
    """Sequential sLSTM over time.  x: (B, S, W) pre-projected gates packed as
    4W (i, f, z, o contributions); recurrent weights act on h."""
    B, S, W4 = x.shape
    W = W4 // 4
    if state is None:
        z = jnp.zeros((B, W), jnp.float32)
        state = (z, z + 1e-6, z, z - 1e9)  # c, n, h, m

    R = p["r"].astype(jnp.float32)  # (W, 4W) recurrent weights

    def step(carry, x_t):
        c, n, h, m = carry
        g = x_t.astype(jnp.float32) + h @ R
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        lf = jax.nn.log_sigmoid(gf)
        m_new = jnp.maximum(lf + m, gi)
        c_new = c * jnp.exp(lf + m - m_new) + jnp.exp(gi - m_new) * jnp.tanh(gz)
        n_new = n * jnp.exp(lf + m - m_new) + jnp.exp(gi - m_new)
        h_new = jax.nn.sigmoid(go) * c_new / jnp.maximum(n_new, 1e-9)
        return (c_new, n_new, h_new, m_new), h_new

    (c, n, h, m), ys = jax.lax.scan(step, state, x.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2).astype(x.dtype), (c, n, h, m)
