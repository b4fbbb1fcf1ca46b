"""Plain float32 reference of a dense decoder with grouped-query attention,
partial rotary embeddings, a gated SiLU MLP, RMSNorm and a tied embedding:
Phi-4-mini (arXiv:2412.08905; the Phi-3 layer of Hugging Face
``modeling_phi3``).

Independent of the program: straightforward ``jax.numpy`` in float32, every
matrix product at ``Precision.HIGHEST``, no cache, no kernels, no sharding.
Departures from the published model, in the reference and the program
alike: plain RoPE (the published ``rope_scaling`` "longrope" is not
implemented; see the configuration's ``reduced``).

The weights are the benchmark's own, made here from the seed; the harness
hands the same values to the program.  Layer weights are stacked on a
leading layer axis and the stack is scanned one layer at a time, so a
full-width model fits beside its bf16 weights.

``quantize="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 with a per-tensor scale (the cotangents too, in a
backward pass), accumulating in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")
E4M3_MAX = 448.0


def dims(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    Dh = c.get("head_dim") or D // H
    rot = int(Dh * c.get("partial_rotary_factor", 1.0))
    return {"D": D, "H": H, "Hkv": c["num_key_value_heads"], "Dh": Dh,
            "F": c["intermediate_size"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"], "rot": rot - rot % 2,
            "theta": c["rope_theta"], "eps": c["rms_norm_eps"]}


def shapes(c: dict) -> dict:
    """name -> (shape, fan_in); fan_in None marks a norm scale."""
    d = dims(c)
    L, D, H, Hkv, Dh, F = d["L"], d["D"], d["H"], d["Hkv"], d["Dh"], d["F"]
    return {
        "embed": ((d["V"], D), D), "norm": ((D,), None),
        "ln1": ((L, D), None), "ln2": ((L, D), None),
        "wq": ((L, D, H, Dh), D), "wk": ((L, D, Hkv, Dh), D),
        "wv": ((L, D, Hkv, Dh), D), "wo": ((L, H, Dh, D), H * Dh),
        "wg": ((L, D, F), D), "wu": ((L, D, F), D), "wd": ((L, F, D), F),
    }


def make_weights(c: dict, key: jax.Array, dtype) -> dict:
    """Weights from ``key``: N(0, 1/fan_in) for matrices, 1 + N(0, 0.1^2)
    for norm scales (so that a dropped scale shows).  Jit it."""
    out = {}
    for i, (name, (shape, fan_in)) in enumerate(sorted(shapes(c).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = 1.0 + 0.1 * x if fan_in is None else x / math.sqrt(fan_in)
        out[name] = x.astype(dtype)
    return out


# ---------------------------------------------------------------- precision

@jax.custom_vjp
def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_fp8(g),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(spec: str, a, b, quantize):
    if quantize == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif quantize is not None:
        raise ValueError(f"unknown quantize {quantize!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- forward

def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, d):
    """Rotate the first ``rot`` dims of each head (halves, as rotate_half)."""
    rot, half = d["rot"], d["rot"] // 2
    inv = 1.0 / d["theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[..., None] * inv          # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _layer(d, lw, h, pos, quantize):
    B, S, _ = h.shape
    Hkv, G, Dh = d["Hkv"], d["H"] // d["Hkv"], d["Dh"]
    x = _rmsnorm(h, lw["ln1"], d["eps"])
    q = _rope(_mm("bsd,dhk->bshk", x, lw["wq"], quantize), pos, d)
    k = _rope(_mm("bsd,dhk->bshk", x, lw["wk"], quantize), pos, d)
    v = _mm("bsd,dhk->bshk", x, lw["wv"], quantize)
    q = q.reshape(B, S, Hkv, G, Dh)                  # query head = kv * G + g
    s = _mm("bskgd,btkd->bkgst", q, k, quantize) / math.sqrt(Dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgst,btkd->bskgd", p, v, quantize).reshape(B, S, d["H"], Dh)
    h = h + _mm("bshk,hkd->bsd", o, lw["wo"], quantize)
    x = _rmsnorm(h, lw["ln2"], d["eps"])
    gate = _mm("bsd,df->bsf", x, lw["wg"], quantize)
    up = _mm("bsd,df->bsf", x, lw["wu"], quantize)
    return h + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lw["wd"], quantize)


def hidden(c: dict, w: dict, tokens, *, quantize=None, remat=False):
    """Final-norm hidden states (B, S, D) of ``tokens`` (B, S)."""
    d = dims(c)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
    h = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)

    def body(h, lw):
        lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
        return _layer(d, lw, h, pos, quantize), None

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, {k: w[k] for k in LAYER_LEAVES})
    return _rmsnorm(h, w["norm"].astype(jnp.float32), d["eps"])


def logits(c: dict, w: dict, tokens, *, quantize=None, remat=False):
    """Logits (B, S, V) in float32; the head is the tied embedding."""
    h = hidden(c, w, tokens, quantize=quantize, remat=remat)
    return _mm("bsd,vd->bsv", h, w["embed"].astype(jnp.float32), quantize)


# ---------------------------------------------------------------- serving

def served_gaps(c: dict, w: dict, tokens, targets):
    """Per position, how far the logit of ``targets`` lies below the best
    logit (0 where ``targets`` < 0).  ``tokens``, ``targets``: (B, S)."""
    lg = logits(c, w, tokens)
    at = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[..., None], -1)[..., 0]
    return jnp.where(targets >= 0, lg.max(-1) - at, 0.0)


def control_gaps(c: dict, w: dict, tokens, targets, quantize: str = "fp8"):
    """Per position compared (``targets`` >= 0), the gap below the
    reference's best logit of the token that the ``quantize`` control puts
    first."""
    ref = logits(c, w, tokens)
    pick = jnp.argmax(logits(c, w, tokens, quantize=quantize), -1)
    at = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return jnp.where(targets >= 0, ref.max(-1) - at, 0.0)


# ---------------------------------------------------------------- training

def _row_loss_sum(c, w, tokens, labels, quantize):
    lg = logits(c, w, tokens, quantize=quantize, remat=True)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).sum()


def loss_and_grad(c: dict, w: dict, tokens, labels, *, quantize=None):
    """Mean next-token cross-entropy over (B, S) and its gradient, one row
    at a time so that a full batch fits."""
    grad_row = jax.value_and_grad(lambda w, t, l: _row_loss_sum(c, w, t[None], l[None], quantize))

    def body(carry, row):
        tot, g = carry
        l, gr = grad_row(w, *row)
        return (tot + l, jax.tree.map(jnp.add, g, gr)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
    (tot, g), _ = jax.lax.scan(body, zero, (tokens, labels))
    n = tokens.size
    return tot / n, jax.tree.map(lambda x: x / n, g)


def lr_at(opt: dict, step):
    """The program's cosine schedule: linear warm-up, then cosine decay to
    ``final_frac`` of the peak over ``total`` steps."""
    step = jnp.asarray(step, jnp.float32)
    peak, warm, total = opt["peak_lr"], opt["warmup"], opt["total"]
    t = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = opt["final_frac"] + (1 - opt["final_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return jnp.where(step < warm, peak * jnp.minimum(step / max(warm, 1), 1.0), peak * cos)


def adamw(opt: dict, w, m, v, g, step):
    """One AdamW update (decoupled weight decay on every leaf)."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    lr = lr_at(opt, step)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    w = jax.tree.map(lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
                     w, m, v)
    return w, m, v


def leaf_norms(tree: dict) -> dict:
    """L2 norm per leaf, and per layer for stacked layer leaves."""
    out = {}
    for k, x in tree.items():
        x = x.astype(jnp.float32)
        if k in LAYER_LEAVES:
            out[k] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[k] = jnp.sqrt(jnp.sum(x * x))
    return out
