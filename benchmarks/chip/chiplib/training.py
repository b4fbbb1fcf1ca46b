"""Training machinery: the feed, the program's first three steps read for
the check, the reference's same three steps, and the comparison.

The numbers compared, each a share of the reference's reading:
* ``loss_gap``   — per step, |program loss - reference loss| / reference
  loss; the worst of the three steps;
* ``grad_gap``   — per leaf (a layer's slice of a stacked weight counts as
  a leaf), |program norm - reference norm| of the first gradient, over the
  larger of that leaf's reference norm and the median leaf's; the worst
  leaf.  The program's gradient is read from its AdamW state after step 1
  (m = (1 - b1) g);
* ``change_gap`` — the same for the parameters' change after three steps,
  as the state that step 4 receives holds them; leaves whose reference
  gradient norm is under a thousandth of the median leaf's are left out
  (they move by round-off alone).

All three are printed; those that ``limits/<workload>.json`` gives a limit
are compared.
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from chiplib.cell import Ctx, seed_key

CHECK_STEPS = 3
STILL = 1e-3


def feed(ctx: Ctx):
    """The jitted feed: (key, step) -> a batch of uniform token rows from
    the configuration's vocabulary, each step's rows its own."""
    B, S = ctx.mix["batch"], ctx.mix["seq"]
    V = ctx.conf["config"]["vocab_size"]

    def batch(key, i):
        x = jax.random.randint(jax.random.fold_in(key, i), (B, S + 1), 0, V, jnp.int32)
        return {"tokens": x[:, :-1], "labels": x[:, 1:]}

    return jax.jit(batch), jax.random.fold_in(seed_key(ctx.seed), 1)


def _host(tree: dict) -> dict:
    return {k: np.asarray(v, np.float64) for k, v in tree.items()}


def reference_steps(ctx: Ctx, *, quantize=None, rows=None) -> dict:
    """The reference's first three steps on the feed's batches, from weights
    made anew from the seed.  ``quantize``: the fp8 control; ``rows``: keep
    only the first rows of each batch (a fault: part of the batch left out,
    the mean over the rest)."""
    ref, c, opt = ctx.ref, ctx.conf["config"], ctx.mix["optimizer"]
    dt = jnp.dtype(c["torch_dtype"])
    make = jax.jit(lambda k: {n: x.astype(jnp.float32)
                              for n, x in ref.make_weights(c, k, dt).items()})

    def step(w, m, v, t, tokens, labels):
        loss, g = ref.loss_and_grad(c, w, tokens, labels, quantize=quantize)
        w, m, v = ref.adamw(opt, w, m, v, g, t)
        return loss, ref.leaf_norms(g), w, m, v

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    change = jax.jit(lambda w, k: ref.leaf_norms(
        jax.tree.map(jnp.subtract, w, make(k))))
    batch, fkey = feed(ctx)
    with jax.default_matmul_precision("highest"):
        w = make(seed_key(ctx.seed))
        m = jax.tree.map(jnp.zeros_like, w)
        v = jax.tree.map(jnp.zeros_like, w)
        losses, g1 = [], None
        for i in range(CHECK_STEPS):
            b = batch(fkey, i)
            toks, labs = (b["tokens"], b["labels"]) if rows is None else (
                b["tokens"][:rows], b["labels"][:rows])
            loss, gn, w, m, v = step(w, m, v, jnp.float32(i + 1), toks, labs)
            losses.append(float(loss))
            if i == 0:
                g1 = _host(gn)
        del m, v
        dn = _host(change(w, seed_key(ctx.seed)))
    del w
    gc.collect()
    return {"loss": losses, "grad": g1, "change": dn}


def _entries(norms: dict) -> dict:
    """Flatten leaf norms to name -> float, one entry per layer slice."""
    out = {}
    for k, v in norms.items():
        v = np.atleast_1d(v)
        for i, x in enumerate(v):
            out[k if len(v) == 1 else f"{k}.{i}"] = float(x)
    return out


def leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Worst leaf of |program norm - reference norm| over the larger of the
    leaf's reference norm and the median leaf's; (gap, leaf)."""
    p, r = _entries(prog), _entries(ref)
    names = [k for k in r if keep is None or k in keep]
    med = float(np.median([r[k] for k in names]))
    gaps = {k: abs(p[k] - r[k]) / max(r[k], med) for k in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def moving(grad: dict) -> set:
    """Leaves whose reference gradient norm is at least a thousandth of the
    median leaf's."""
    r = _entries(grad)
    med = float(np.median(list(r.values())))
    return {k for k, x in r.items() if x >= STILL * med}


def compare(prog: dict, ref: dict) -> dict:
    """name -> (number, worst leaf or step) for the three numbers."""
    lg = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])]
    worst = int(np.argmax(lg))
    keep = moving(ref["grad"])
    change, leaf = leaf_gap(prog["change"], ref["change"], keep)
    return {"loss_gap": (lg[worst], f"step {worst + 1}"),
            "grad_gap": leaf_gap(prog["grad"], ref["grad"]),
            "change_gap": (change, f"{leaf} ({len(keep)} of {len(_entries(ref['grad']))} "
                                   f"leaves counted)")}
