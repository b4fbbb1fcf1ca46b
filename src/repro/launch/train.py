"""End-to-end training driver.

CPU-runnable with tiny configs (``--tiny``); full configs target the
production mesh (compile-proven by dryrun.py).  Wires the data pipeline,
sharded train step, checkpoint/restart, and straggler monitoring.
``main(argv, cfg=...)`` runs the same loop on a caller's ``ModelConfig``
(e.g. a published config cut in depth).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-32b --tiny \
        --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_tiny_config
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.training.checkpoint import CheckpointManager
from repro.training.data import SyntheticTokenPipeline
from repro.training.fault_tolerance import StepMonitor, run_with_restarts
from repro.training.optimizer import make_optimizer
from repro.training.train_step import make_train_step
from repro.models import Model


DEFAULT_CKPT_DIR = Path(__file__).resolve().parents[3] / "results" / "ckpt"


def main(argv=None, *, cfg: ModelConfig | None = None) -> dict:
    """Train; ``cfg`` (when given) replaces ``--arch``/``--tiny``.

    Returns the run's record: ``steps_run`` (steps this call executed — 0
    when the checkpoint directory already holds a run that reached
    ``--steps``), per-step ``loss``, ``grad_norm`` and ``step_s``, and the
    last saved step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b", choices=list(ARCH_IDS))
    ap.add_argument("--tiny", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "adafactor"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "block", "dots"])
    ap.add_argument("--ckpt-dir", default=str(DEFAULT_CKPT_DIR))
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    if cfg is None:
        cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    run = RunConfig(model=cfg, shape=shape, optimizer=args.optimizer,
                    microbatches=args.microbatches, remat_policy=args.remat)
    optimizer = make_optimizer(args.optimizer)
    step_fn = jax.jit(make_train_step(cfg, run, optimizer), donate_argnums=(0,))

    model = Model(cfg, remat_policy=args.remat)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    monitor = StepMonitor()
    record = {"steps_run": 0, "loss": [], "grad_norm": [], "step_s": []}

    def train_loop(start_step: int) -> int:
        if start_step >= args.steps:
            print(f"nothing to run: {args.ckpt_dir} already holds step "
                  f"{start_step - 1} of --steps {args.steps}")
            return start_step
        params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
        state = {"params": params, "opt": optimizer.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        pipe_start = 0
        if start_step > 0:
            state, extra = ckpt.restore(state)
            pipe_start = extra.get("data_step", start_step)
            print(f"[restore] resumed at step {start_step}")
        pipe = SyntheticTokenPipeline(cfg, global_batch=args.batch,
                                      seq_len=args.seq, seed=args.seed,
                                      start_step=pipe_start)
        last_loss = float("nan")
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            monitor.start()
            state, metrics = step_fn(state, batch)
            last_loss = float(metrics["loss"])
            dt = monitor.stop()
            grad_norm = float(metrics["grad_norm"])
            record["steps_run"] += 1
            record["loss"].append(last_loss)
            record["grad_norm"].append(grad_norm)
            record["step_s"].append(dt)
            print(f"step {step:5d} loss {last_loss:8.4f} "
                  f"grad_norm {grad_norm:8.3f} {dt*1e3:7.1f} ms", flush=True)
            if (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps:
                ckpt.save(step, state, extra={"data_step": pipe.state()["step"],
                                              "loss": last_loss})
        pipe.close()
        print(f"done. mean step {monitor.mean_step_s*1e3:.1f} ms; "
              f"stragglers: {len(monitor.stragglers)}")
        return args.steps

    run_with_restarts(train_loop, ckpt,
                      on_restart=lambda n, e: print(f"[restart {n}] {e}"))
    record["saved_step"] = ckpt.latest_step()
    return record


if __name__ == "__main__":
    main()
