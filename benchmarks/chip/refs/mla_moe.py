"""Plain float32 reference of DeepSeek-V3's decoder (arXiv:2412.19437; the
published ``config.json`` and ``modeling_deepseek``), for one chip's share
of its expert layers.

Independent of the program: straightforward ``jax.numpy`` in float32, every
matrix product at ``Precision.HIGHEST``, no cache, no kernels, no batching
of experts.  The layer:

* multi-head latent attention in its expanded form: q through the
  ``q_lora_rank`` bottleneck and its RMSNorm; keys and values from the
  normalised ``kv_lora_rank`` latent; a rotary part of ``qk_rope_head_dim``
  dims, per head for q and one shared by all heads for k; softmax scale
  (qk_nope + qk_rope)^-1/2 times YaRN's mscale(factor, mscale_all_dim)^2;
* YaRN (``rope_scaling`` "yarn"): per rotary pair, the original frequency,
  the frequency over ``factor``, or a linear ramp between, by how often the
  pair turns over ``original_max_position_embeddings`` (between
  ``beta_slow`` and ``beta_fast`` turns); cos and sin times
  mscale(factor, mscale) / mscale(factor, mscale_all_dim);
* the first ``first_k_dense_replace`` layers a gated SiLU MLP of
  ``intermediate_size``; the others the MoE layer: the noaux_tc router over
  all ``router_experts`` (sigmoid scores, the correction bias added for the
  choice only, groups scored by their two best, the ``topk_group`` best
  groups kept, the top ``num_experts_per_tok`` there, the chosen sigmoid
  scores normalised and times ``routed_scaling_factor``); each expert this
  chip holds computed on exactly the tokens routed to it (gathered, then
  added back weighted); the shared expert on every token;
* RMSNorm before each half, a final RMSNorm and an untied head.

Departures from the published model, in the reference and the program
alike: rotary pairs are (i, i + rope/2) where the checkpoint interleaves
them (a fixed permutation of the rope projections' columns, the same model
under other weights); no MTP layer; bf16 weights where the release is FP8;
the parts of the experts this chip does not hold are left out, and that
partial result goes on to the next layer (the configuration's
``expert_share``: ``n_routed_experts`` of the router's ``router_experts``
are held, from ``expert_offset``).

The weights are the benchmark's own, made here from the seed; the harness
hands the same values to the program.  MoE layers are stacked on a leading
layer axis and scanned, the dense lead is a list; each weight is upcast
where it is used and attention runs in query blocks, so that a row of
8192 positions fits beside the bf16 weights.

``quantize="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 with a per-tensor scale, accumulating in float32.

The served-token comparison holds each request to its widest gap after
setting aside at most ``FLIP_SHARE`` of its compared positions, each of
them alone: where two neighbouring positions both read a gap, the smaller
counts.  bf16 rounding of the residual stream moves the router's scores
by ~0.002 (median; 0.016 at the 99.9th percentile) in the first MoE
layer, and at a near-tie that carries a held expert across the top-k
boundary and the position's output by a whole expert's share.  Such flips
are isolated and rare (at most 5 of 1024 positions above 0.4, never two
side by side, on the CPU at full widths); a wrong router, scale, rotation
or expert moves most positions, and a fault confined to a stretch of a
row, such as a cache or rotation error late in a long context, moves
neighbouring ones.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
Q_BLOCK = 256
# Most of a request's compared positions that the served-token comparison
# sets aside for router near-ties flipped by bf16 rounding: 2.4-2.7 % of
# the positions read a gap above 0.1 with every position compared (CPU,
# full widths, 7 layers, 1024 positions, 3 seeds; PERF.md section 6).
FLIP_SHARE = 0.03


def dims(c: dict) -> dict:
    """Sizes under short names.  ``expert_share`` (the configuration's
    cut): the router's width and the first expert this chip holds; without
    it the chip holds every expert."""
    rs = c.get("rope_scaling") or {}
    share = c.get("expert_share", {})
    return {"D": c["hidden_size"], "H": c["num_attention_heads"], "qr": c["q_lora_rank"],
            "kvr": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "F": c["intermediate_size"],
            "Fm": c["moe_intermediate_size"], "Fs": c["moe_intermediate_size"] * c["n_shared_experts"],
            "lead": c["first_k_dense_replace"],
            "moe": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "E": share.get("router_experts", c["n_routed_experts"]),
            "held": c["n_routed_experts"], "offset": share.get("expert_offset", 0),
            "K": c["num_experts_per_tok"], "G": c["n_group"], "topk_group": c["topk_group"],
            "scale": c["routed_scaling_factor"], "norm_topk": c["norm_topk_prob"],
            "V": c["vocab_size"], "theta": c["rope_theta"], "eps": c["rms_norm_eps"],
            "yarn": rs if rs.get("type") == "yarn" else None}


def _attn_shapes(d: dict) -> dict:
    D, H, qr, kvr = d["D"], d["H"], d["qr"], d["kvr"]
    return {"ln1": ((D,), None), "wdq": ((D, qr), D), "q_norm": ((qr,), None),
            "wuq": ((qr, H, d["dn"] + d["dr"]), qr), "wdkv": ((D, kvr), D),
            "kv_norm": ((kvr,), None), "wuk": ((kvr, H, d["dn"]), kvr),
            "wuv": ((kvr, H, d["dv"]), kvr), "wkr": ((D, d["dr"]), D),
            "wo": ((H, d["dv"], D), H * d["dv"]), "ln2": ((D,), None)}


def shapes(c: dict) -> dict:
    """name -> (shape, fan_in) per group; fan_in None marks a norm scale,
    0 the router's correction bias.  ``lead``: one layer's shapes (one set
    per dense layer); ``moe``: stacked over the MoE layers."""
    d = dims(c)
    D, F, Fm, Fs, E, n = d["D"], d["F"], d["Fm"], d["Fs"], d["held"], d["moe"]
    lead = dict(_attn_shapes(d), wg=((D, F), D), wu=((D, F), D), wd=((F, D), F))
    moe = dict(_attn_shapes(d), router=((D, d["E"]), D), router_bias=((d["E"],), 0),
               eg=((E, D, Fm), D), eu=((E, D, Fm), D), ed=((E, Fm, D), Fm),
               sg=((D, Fs), D), su=((D, Fs), D), sd=((Fs, D), Fs))
    return {"top": {"embed": ((d["V"], D), D), "head": ((D, d["V"]), D), "norm": ((D,), None)},
            "lead": lead, "moe": {k: ((n, *s), f) for k, (s, f) in moe.items()}}


def make_weights(c: dict, key: jax.Array, dtype) -> dict:
    """Weights from ``key``: N(0, 1/fan_in) matrices, 1 + N(0, 0.1^2) norm
    scales and N(0, 0.1^2) router biases (so that a dropped scale or bias
    shows).  ``{"embed", "head", "norm", "lead": [layer, ...], "moe":
    {stacked}}``.  Jit it."""
    sh = shapes(c)
    count = iter(range(1 << 20))

    def draw(shape, fan_in):
        x = jax.random.normal(jax.random.fold_in(key, next(count)), shape, jnp.float32)
        if fan_in is None:
            x = 1.0 + 0.1 * x
        elif fan_in == 0:
            x = 0.1 * x
        else:
            x = x / math.sqrt(fan_in)
        return x.astype(dtype)

    out = {k: draw(*v) for k, v in sorted(sh["top"].items())}
    out["lead"] = [{k: draw(*v) for k, v in sorted(sh["lead"].items())}
                   for _ in range(dims(c)["lead"])]
    out["moe"] = {k: draw(*v) for k, v in sorted(sh["moe"].items())}
    return out


# ---------------------------------------------------------------- precision

def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, quantize):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quantize == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif quantize is not None:
        raise ValueError(f"unknown quantize {quantize!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- forward

def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(d: dict):
    """Inverse frequencies of the rotary pairs and the factor on cos and
    sin, as ``DeepseekV3YarnRotaryEmbedding`` computes them."""
    dim, base = d["dr"], d["theta"]
    freq_extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    y = d["yarn"]
    if y is None:
        return freq_extra, 1.0
    factor, orig = y["factor"], y["original_max_position_embeddings"]
    freq_inter = freq_extra / factor

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    mag = _get_mscale(factor, y.get("mscale", 1)) / _get_mscale(factor, y.get("mscale_all_dim", 0))
    return inv, mag


def softmax_scale(d: dict) -> float:
    scale = (d["dn"] + d["dr"]) ** -0.5
    y = d["yarn"]
    if y is not None and y.get("mscale_all_dim"):
        scale *= _get_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope(x, pos, d):
    """Rotate (..., S, [H,] dr) by position; pairs (i, i + dr/2)."""
    inv, mag = rope_tables(d)
    ang = pos.astype(jnp.float32)[:, :, None] * inv                # (B, S, dr/2)
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    half = d["dr"] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(d, lw, x, pos, quantize):
    B, S, _ = x.shape
    dn = d["dn"]
    cq = _rmsnorm(_mm("bsd,dr->bsr", x, lw["wdq"], quantize), lw["q_norm"], d["eps"])
    q = _mm("bsr,rhk->bshk", cq, lw["wuq"], quantize)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, d)
    ckv = _rmsnorm(_mm("bsd,dr->bsr", x, lw["wdkv"], quantize), lw["kv_norm"], d["eps"])
    k_nope = _mm("bsr,rhk->bshk", ckv, lw["wuk"], quantize)
    v = _mm("bsr,rhk->bshk", ckv, lw["wuv"], quantize)
    k_pe = _rope(_mm("bsd,dk->bsk", x, lw["wkr"], quantize), pos, d)      # (B, S, dr)
    scale = softmax_scale(d)
    qb = min(Q_BLOCK, S)
    n_blk = -(-S // qb)
    pad = n_blk * qb - S

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(jnp.pad(q_nope, ((0, 0), (0, pad), (0, 0), (0, 0))),
                                          i * qb, qb, 1)
        qp = jax.lax.dynamic_slice_in_dim(jnp.pad(q_pe, ((0, 0), (0, pad), (0, 0), (0, 0))),
                                          i * qb, qb, 1)
        s = (_mm("bqhk,bthk->bhqt", qn, k_nope, quantize)
             + _mm("bqhk,btk->bhqt", qp, k_pe, quantize)) * scale
        causal = jnp.arange(S)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("bhqt,bthk->bqhk", p, v, quantize)

    o = jax.lax.map(block, jnp.arange(n_blk))                        # (n_blk, B, qb, H, dv)
    o = jnp.moveaxis(o, 0, 1).reshape(B, n_blk * qb, d["H"], d["dv"])[:, :S]
    return _mm("bshk,hkd->bsd", o, lw["wo"], quantize)


def _swiglu(x, wg, wu, wd, quantize):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", x, wg, quantize))
               * _mm("...d,df->...f", x, wu, quantize), wd, quantize)


def route(d, lw, x, quantize=None):
    """The noaux_tc router on (T, D): expert ids (T, K) and weights (T, K)."""
    scores = jax.nn.sigmoid(_mm("td,de->te", x, lw["router"], quantize))
    choice = scores + lw["router_bias"].astype(jnp.float32)
    T, E, G = x.shape[0], d["E"], d["G"]
    group_score = jnp.sort(choice.reshape(T, G, E // G), axis=-1)[..., -2:].sum(-1)
    top_groups = jnp.argsort(-group_score, axis=-1)[:, :d["topk_group"]]
    in_top = (jnp.arange(G)[None, :, None] == top_groups[:, None, :]).any(-1)     # (T, G)
    choice = jnp.where(jnp.repeat(in_top, E // G, axis=1), choice, -jnp.inf)
    ids = jnp.argsort(-choice, axis=-1)[:, :d["K"]]
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if d["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return ids, w * d["scale"]


def moe(d, lw, x, quantize=None, experts=None):
    """The MoE layer's output on (B, S, D): the routed experts ``experts``
    (global ids; default the held ones) on exactly the tokens routed to
    each, plus the shared expert."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    T = xf.shape[0]
    ids, w = route(d, lw, xf, quantize)
    xpad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)])
    out = _swiglu(xf, lw["sg"], lw["su"], lw["sd"], quantize)
    for e in (range(d["offset"], d["offset"] + d["held"]) if experts is None else experts):
        j = e - d["offset"]
        hit = ids == e                                                   # (T, K)
        rows = jnp.nonzero(hit.any(-1), size=T, fill_value=T)[0]
        y = _swiglu(xpad[rows], lw["eg"][j], lw["eu"][j], lw["ed"][j], quantize)
        wt = jnp.concatenate([jnp.where(hit, w, 0.0).sum(-1), jnp.zeros((1,))])[rows]
        out = out.at[rows].add(wt[:, None] * y, mode="drop")
    return out.reshape(B, S, D)


def _layer(d, lw, h, pos, quantize, kind):
    h = h + _attention(d, lw, _rmsnorm(h, lw["ln1"], d["eps"]), pos, quantize)
    x = _rmsnorm(h, lw["ln2"], d["eps"])
    if kind == "dense":
        return h + _swiglu(x, lw["wg"], lw["wu"], lw["wd"], quantize)
    return h + moe(d, lw, x, quantize)


def hidden(c: dict, w: dict, tokens, *, quantize=None):
    """Final-norm hidden states (B, S, D) of ``tokens`` (B, S)."""
    d = dims(c)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
    h = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    for lw in w["lead"]:
        h = _layer(d, lw, h, pos, quantize, "dense")
    h, _ = jax.lax.scan(lambda h, lw: (_layer(d, lw, h, pos, quantize, "moe"), None), h,
                        w["moe"])
    return _rmsnorm(h, w["norm"], d["eps"])


def logits(c: dict, w: dict, tokens, *, quantize=None):
    """Logits (B, S, V) in float32 over the vocabulary's slice."""
    return _mm("bsd,dv->bsv", hidden(c, w, tokens, quantize=quantize), w["head"], quantize)


# ---------------------------------------------------------------- serving

def share_gap(gaps, targets):
    """Per row, the widest of ``gaps`` over the compared positions
    (``targets`` >= 0) once at most ``FLIP_SHARE`` of them are set aside,
    each alone: the largest gap past that many, or the smaller gap of two
    neighbouring positions, whichever is wider."""
    on = targets >= 0
    g = jnp.where(on, gaps, 0.0)
    cap = jnp.ceil(FLIP_SHARE * on.sum(-1)).astype(jnp.int32)
    desc = jnp.concatenate([-jnp.sort(-g, axis=-1), jnp.zeros_like(g[..., :1])], -1)
    past = jnp.take_along_axis(desc, cap[..., None], -1)[..., 0]
    pair = jnp.minimum(g[..., 1:], g[..., :-1]).max(-1, initial=0.0)
    return jnp.maximum(past, pair)


def served_gaps(c: dict, w: dict, tokens, targets):
    """How far the logit of ``targets`` lies below the best logit, with
    the router's flips set aside (:func:`share_gap`).
    ``tokens``, ``targets``: (1, S); ``targets`` < 0 where nothing was
    served."""
    lg = logits(c, w, tokens)
    at = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[..., None], -1)[..., 0]
    return share_gap(lg.max(-1) - at, targets)


def control_gaps(c: dict, w: dict, tokens, targets, quantize: str = "fp8"):
    """As :func:`served_gaps` reads the served tokens, the gap below the
    reference's best logit of the token that the ``quantize`` control puts
    first."""
    ref = logits(c, w, tokens)
    pick = jnp.argmax(logits(c, w, tokens, quantize=quantize), -1)
    at = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return share_gap(ref.max(-1) - at, targets)
