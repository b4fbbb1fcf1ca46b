"""Sharded training step: loss, grad accumulation, optimizer, ZeRO specs.

``make_train_step`` returns the jittable step plus the sharding trees needed
by the launcher / dry-run: params, optimizer state (ZeRO-staged), batch.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.distributed.sharding import ShardingEnv, fsdp_spec, resolve_spec
from repro.models import Model, abstract_params, param_logical_axes
from repro.training.optimizer import Optimizer, maybe_compress

Pytree = Any


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Mean next-token CE over labels >= 0.  logits f32 (B,S,V); labels (B,S)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    tok = jnp.maximum(mask.sum(), 1.0)
    return -(ll * mask).sum() / tok, tok


def make_loss_fn(model: Model):
    def loss_fn(params, batch):
        logits, aux = model.forward(params, batch)
        ce, tok = cross_entropy(logits, batch["labels"])
        return ce + aux, {"loss": ce + aux, "ce": ce, "aux_loss": aux, "tokens": tok}
    return loss_fn


# --------------------------------------------------------------------------
# Sharding specs (params / optimizer state / batch)
# --------------------------------------------------------------------------

def param_pspecs(cfg: ModelConfig, env: ShardingEnv, zero_stage: int) -> Pytree:
    axes = param_logical_axes(cfg)
    shapes = abstract_params(cfg)

    def f(ax, sds):
        skip = 1 if ax and ax[0] == "layer" else 0
        if zero_stage >= 3:
            return fsdp_spec(env, ax, sds.shape, skip_leading=skip)
        return resolve_spec(env, ax, sds.shape)

    return jax.tree.map(f, axes, shapes, is_leaf=lambda x: isinstance(x, tuple))


def _moment_spec(env, ax, shape, zero_stage):
    """Spec for an fp32 moment with same shape as its param: ZeRO>=1 shards
    optimizer state over the data axis."""
    skip = 1 if ax and ax[0] == "layer" else 0
    if zero_stage >= 1:
        return fsdp_spec(env, ax, shape, skip_leading=skip)
    return resolve_spec(env, ax, shape)


def opt_pspecs(cfg: ModelConfig, env: ShardingEnv, run: RunConfig) -> Pytree:
    axes = param_logical_axes(cfg)
    shapes = abstract_params(cfg)
    zs = run.zero_stage

    if run.optimizer == "adamw":
        mspec = jax.tree.map(lambda ax, s: _moment_spec(env, ax, s.shape, zs),
                             axes, shapes, is_leaf=lambda x: isinstance(x, tuple))
        return {"m": mspec, "v": mspec, "step": P()}

    # adafactor: flat list aligned with param leaves
    ax_leaves = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    sh_leaves = jax.tree.leaves(shapes)
    f_specs = []
    for ax, s in zip(ax_leaves, sh_leaves):
        shape = s.shape
        if len(shape) >= 2 and shape[-1] > 1 and shape[-2] > 1:
            f_specs.append({
                "vr": _moment_spec(env, ax[:-1], shape[:-1], zs),
                "vc": _moment_spec(env, (*ax[:-2], ax[-1]), (*shape[:-2], shape[-1]), zs),
            })
        else:
            f_specs.append({"v": _moment_spec(env, ax, shape, zs)})
    return {"f": f_specs, "step": P()}


def batch_pspecs(cfg: ModelConfig, env: ShardingEnv, global_batch: int,
                 *, kind: str = "train") -> dict:
    """Specs resolved against the *actual* batch size (long_500k has batch=1,
    which must degrade to replicated)."""
    bs = resolve_spec(env, ("batch",), (global_batch,))
    batch_axes = bs[0] if len(bs) else None
    specs = {"tokens": P(batch_axes, None)}
    if kind == "train":
        specs["labels"] = P(batch_axes, None)
    if cfg.rope_style == "mrope":
        specs["positions"] = P(batch_axes, None, None)
    if kind != "decode":   # modality stubs feed prefill/train only
        if cfg.encoder_layers > 0:
            specs["frame_embeds"] = P(batch_axes, None, None)
        if cfg.frontend == "vision_patches":
            specs["patch_embeds"] = P(batch_axes, None, None)
    return specs


# --------------------------------------------------------------------------
# Train step
# --------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, run: RunConfig, optimizer: Optimizer):
    model = Model(cfg, remat_policy=run.remat_policy)
    loss_fn = make_loss_fn(model)
    k = run.microbatches

    def train_step(state, batch):
        params = state["params"]
        if k <= 1:
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        else:
            micro = jax.tree.map(lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]), batch)

            def acc_step(carry, mb):
                g_acc, m_acc = carry
                (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32) / k, g_acc, g)
                m_acc = jax.tree.map(lambda a, b: a + b / k, m_acc, m)
                return (g_acc, m_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = {"loss": 0.0, "ce": 0.0, "aux_loss": 0.0, "tokens": 0.0}
            m0 = jax.tree.map(jnp.float32, m0)
            (grads, metrics), _ = jax.lax.scan(acc_step, (g0, m0), micro)
        grads = maybe_compress(grads, run.grad_compression)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, state["opt"], params)
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        metrics = dict(metrics)
        metrics["grad_norm"] = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)))
        return new_state, metrics

    return train_step


def state_pspecs(cfg: ModelConfig, env: ShardingEnv, run: RunConfig) -> dict:
    return {
        "params": param_pspecs(cfg, env, run.zero_stage),
        "opt": opt_pspecs(cfg, env, run),
        "step": P(),
    }


def to_named(env: ShardingEnv, tree: Pytree) -> Pytree:
    return jax.tree.map(lambda s: NamedSharding(env.mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def jit_sharded_train_step(cfg: ModelConfig, run: RunConfig,
                           optimizer: Optimizer, env: ShardingEnv):
    """The train step jitted over ``env.mesh``: state (ZeRO-staged) and
    batch sharded by the logical-axis rules, state donated.  Returns
    ``(step, state_shardings)``; trace and call it under ``activate(env)``
    so the model's activation constraints resolve on the same mesh."""
    s_ns = to_named(env, state_pspecs(cfg, env, run))
    b_ns = to_named(env, batch_pspecs(cfg, env, run.shape.global_batch))
    step = jax.jit(make_train_step(cfg, run, optimizer),
                   in_shardings=(s_ns, b_ns), out_shardings=(s_ns, None),
                   donate_argnums=(0,))
    return step, s_ns
