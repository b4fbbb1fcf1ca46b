"""Chip smoke: the JAX execution path and the simulator on one TPU, in one process.

    python chip_smoke.py             # one chip: device, serve, train, simulator
    python chip_smoke.py --chips 4   # four chips: sharded train step vs one chip

Every phase goes through the repo's own entry points at phi4-mini-3.8b's
published widths, on random weights made from ``--seed``:

* device — a TPU whose ``device_kind`` the simulator's hardware table knows;
* serve — the published config through ``launch/serve.py`` and
  ``ServingEngine``; the decode path's logits are checked against
  ``Model.forward`` on the same tokens;
* train — 2 of the 32 layers at published widths through the loop of
  ``launch/train.py``, checkpoint included;
* simulator — predictions of the served decode step and the train step,
  and a sweep on a worker pool that must rank as the serial sweep does
  while no worker touches the chip.

``--chips 4`` runs only the device phase and the 2-layer train step sharded
on a 2x2 ``("data", "model")`` mesh against the same steps on one chip.

Times printed here are a smoke, not a benchmark.  Without a TPU, or on any
failed check, the script exits non-zero and prints no result.  On success
the last line of stdout is the only JSON object it prints:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

ARCH = "phi4-mini-3.8b"
SERVE = dict(requests=8, min_len=64, max_len=1025, max_new=32, slots=4,
             cache_len=2048)
TRAIN = dict(layers=2, batch=1, seq=1024, steps=3)
SHARDED = dict(batch=4, seq=256, steps=3)
CKPT_DIR = REPO / "results" / "chip_smoke_ckpt"
# decode-path vs full-forward logits, relative L2 error.  The two paths
# compute the same function but at other shapes (one row against a padded
# cache vs the whole causal sequence), so XLA may round each layer's bf16
# output differently by about one ulp (2**-8); over a residual stream of L
# layers such differences add like a random walk.  Limit: twice that,
# 2 * sqrt(L) * 2**-8 (4.4e-2 at 32 layers).
def logit_rtol(num_layers: int) -> float:
    return 2 * math.sqrt(num_layers) * 2.0 ** -8

# sharded vs one-chip loss per step (absolute; the loss is ~ln(vocab) ~ 12):
# TP partial sums are reduced across chips in another order.
LOSS_ATOL = 1e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def _maps_libtpu(pid: int | str) -> bool:
    """Whether a process has the TPU runtime library mapped (Linux)."""
    return "libtpu" in Path(f"/proc/{pid}/maps").read_text()


# ---------------------------------------------------------------- phases

def device_phase(chips: int):
    """The TPU devices and the simulator's spec of their kind."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    say(f"[device] platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devices)}")
    check(d0.platform == "tpu", f"no TPU: jax runs on {d0.platform!r}")
    check(len(devices) >= chips, f"{chips} chips asked, {len(devices)} found")
    from repro.core.backend.hardware import hardware_for_device_kind
    hw = hardware_for_device_kind(d0.device_kind)
    say(f"[device] simulator spec: {hw.name}")
    return devices, hw


def serve_phase(cfg, *, requests: int, min_len: int, max_len: int,
                max_new: int, slots: int, cache_len: int, seed: int) -> dict:
    """Serve seeded requests through launch/serve.py; check the outputs and
    the decode path's logits against ``Model.forward``.  Returns the warm
    decode step time (s)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve as serve_cli

    say(f"[serve] {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads}, vocab {cfg.vocab_size}, "
        f"{cfg.param_dtype}, {cfg.param_count() / 1e9:.2f} B params; "
        f"{requests} requests, prompts {min_len}-{max_len - 1} tokens, "
        f"{max_new} new tokens, {slots} slots x {cache_len} cache")
    reqs = serve_cli.make_requests(cfg, requests, min_len=min_len,
                                   max_len=max_len, max_new=max_new, seed=seed)
    engine, finished, wall = serve_cli.serve(cfg, reqs, slots=slots,
                                             cache_len=cache_len, seed=seed)
    check(len(finished) == requests,
          f"{len(finished)} of {requests} requests finished")
    for r in finished:
        check(len(r.tokens) == max_new,
              f"request {r.rid} made {len(r.tokens)} of {max_new} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"request {r.rid} has token ids outside the vocabulary")
    check(all(bool(jnp.isfinite(c).all())
              for c in jax.tree.leaves(engine.cache["blocks"])),
          "the engine's KV cache holds non-finite values")
    toks = sum(len(r.tokens) for r in finished)
    ttft_ms = sorted(r.ttft_s * 1e3 for r in finished)
    say(f"[serve] smoke, not a benchmark: {toks} tokens in {wall:.3f} s "
        f"({toks / wall:.1f} tok/s), TTFT ms p50 "
        f"{ttft_ms[len(ttft_ms) // 2]:.1f} max {ttft_ms[-1]:.1f}; "
        f"compiles included")

    # warm decode step of the served shape (slots x cache_len)
    params, cache, last = engine.params, engine.cache, engine._last_tok
    logits, cache = engine._decode(params, cache, {"tokens": last})
    jax.block_until_ready(logits)
    n = 5
    t0 = time.perf_counter()
    for _ in range(n):
        logits, cache = engine._decode(params, cache, {"tokens": last})
        jax.block_until_ready(logits)
    decode_s = (time.perf_counter() - t0) / n
    say(f"[serve] smoke, not a benchmark: warm decode step "
        f"{decode_s * 1e3:.3f} ms at batch {slots}, cache {cache_len}")
    del cache, logits

    # the decode path (prefill + decode_step) vs Model.forward, same tokens
    r = max(finished, key=lambda r: len(r.prompt))
    model = engine.model
    _, pc = model.prefill(params, {"tokens": jnp.asarray([r.prompt], jnp.int32)},
                          cache_len=cache_len)
    decode = jax.jit(model.decode_step)
    agree = 0
    for t, nxt in zip(r.tokens[:-1], r.tokens[1:]):
        logits, pc = decode(params, pc, {"tokens": jnp.asarray([[t]], jnp.int32)})
        agree += int(jnp.argmax(logits[0, 0])) == nxt
    dec = np.asarray(logits[0, 0], np.float32)
    full, _ = jax.jit(model.forward)(
        params, {"tokens": jnp.asarray([r.prompt + r.tokens[:-1]], jnp.int32)})
    ref = np.asarray(full[0, -1], np.float32)
    check(np.isfinite(dec).all() and np.isfinite(ref).all(),
          "non-finite logits")
    rel = float(np.linalg.norm(dec - ref) / np.linalg.norm(ref))
    limit = logit_rtol(cfg.num_layers)
    say(f"[serve] request {r.rid} ({len(r.prompt)} prompt + {max_new} tokens): "
        f"decode-path vs forward logits at the last position: rel L2 "
        f"{rel:.3e} (limit {limit:.3e}), max abs "
        f"{float(np.abs(dec - ref).max()):.3e}; batch-1 replay picks the "
        f"served token at {agree}/{max_new - 1} steps")
    check(rel <= limit, f"decode logits differ from forward: {rel:.3e}")
    return {"decode_step_s": decode_s}


def train_phase(cfg, *, batch: int, seq: int, steps: int, ckpt_dir: Path,
                seed: int) -> dict:
    """``steps`` steps through the loop of launch/train.py from a fresh
    checkpoint directory; the final save is kept."""
    from repro.launch import train as train_cli

    shutil.rmtree(ckpt_dir, ignore_errors=True)
    record = train_cli.main(
        ["--steps", str(steps), "--batch", str(batch), "--seq", str(seq),
         "--optimizer", "adamw", "--remat", "none", "--ckpt-every",
         str(steps), "--ckpt-dir", str(ckpt_dir), "--seed", str(seed)],
        cfg=cfg)
    check(record["steps_run"] == steps,
          f"{record['steps_run']} of {steps} train steps ran")
    check(all(math.isfinite(x) for x in record["loss"] + record["grad_norm"]),
          f"non-finite loss or grad norm: {record}")
    check(record["saved_step"] == steps - 1,
          f"last checkpoint is step {record['saved_step']}")
    say(f"[train] smoke, not a benchmark: losses {record['loss']}, grad norms "
        f"{record['grad_norm']}, step s {record['step_s']} (the first "
        f"compiles); checkpoint of step {record['saved_step']} kept in "
        f"{ckpt_dir}")
    return record


def sim_phase(hw, serve_cfg, train_cfg, *, slots: int, cache_len: int,
              batch: int, seq: int, decode_step_s: float,
              train_step_s: float) -> None:
    """Predict the served decode step and the train step; then check that a
    pooled sweep ranks as the serial one and its workers stay off the chip."""
    import multiprocessing as mp

    from repro.api import (Cluster, DecodeWorkload, SimSpec, SweepSpace,
                           TrainWorkload, sweep)
    from repro.api.pool import get_pool, shutdown_pools
    from repro.core import Simulator
    from repro.core.backend.profiling import ProfileDB

    sim = Simulator(hw, db=ProfileDB(None))
    check(not sim.cache.persistent, "the simulator loaded a persistent cache")
    dec = sim.run(SimSpec(serve_cfg, Cluster(hw), workload=DecodeWorkload(
        global_batch=slots, seq_len=cache_len, cache_len=cache_len)))
    tr = sim.run(SimSpec(train_cfg, Cluster(hw), workload=TrainWorkload(
        global_batch=batch, seq_len=seq, remat="none", optimizer="adamw")))
    say(f"[sim] smoke, not a benchmark: decode step (batch {slots}, cache "
        f"{cache_len}) predicted {dec.step_time_us / 1e3:.3f} ms, measured "
        f"{decode_step_s * 1e3:.3f} ms; train step ({train_cfg.num_layers} "
        f"layers, batch {batch}, seq {seq}) predicted "
        f"{tr.step_time_us / 1e3:.3f} ms, measured {train_step_s * 1e3:.3f} ms")

    space = SweepSpace(
        SimSpec(serve_cfg, Cluster(hw, chips=4, memory_limit=hw.hbm_bytes),
                workload=DecodeWorkload(seq_len=cache_len)),
        {"tp": (1, 2, 4), "batch": (slots, 2 * slots, 4 * slots)})
    serial = sweep(space, sim=sim)
    pooled = sweep(space, workers=2)
    ranking = [(r.cand.key(), r.report.step_time_us) for r in serial.ranked()]
    check(len(ranking) > 0, "the serial sweep ranked nothing")
    check(not pooled.failed, f"pooled sweep candidates failed: {pooled.failed}")
    check([(r.cand.key(), r.report.step_time_us) for r in pooled.ranked()]
          == ranking, "the workers=2 sweep ranks differently from the serial")
    workers = [p for p in mp.active_children()
               if p.name.startswith("charon-sweep")]
    check(len(workers) == 2, f"{len(workers)} pool workers alive, 2 expected")
    on_chip = [p.pid for p in workers if _maps_libtpu(p.pid)]
    say(f"[sim] sweep of {len(ranking)} candidates: workers=2 ({get_pool(2).context_name}) "
        f"ranks as serial; TPU runtime mapped in this process: "
        f"{_maps_libtpu('self')}, in pool workers: {len(on_chip)}/2")
    check(not on_chip, f"pool workers {on_chip} loaded the TPU runtime")
    shutdown_pools()


def sharded_train_phase(cfg, devices, *, batch: int, seq: int, steps: int,
                        seed: int) -> None:
    """The train step sharded on a 2x2 ("data", "model") mesh by the
    dry-run's rules vs the same steps on ``devices[0]`` alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.distributed.sharding import ShardingEnv, activate
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.training.data import SyntheticTokenPipeline
    from repro.training.optimizer import make_optimizer
    from repro.training.train_step import jit_sharded_train_step, make_train_step

    run = RunConfig(model=cfg, shape=ShapeConfig("smoke", seq, batch, "train"),
                    data=2, model_axis=2, zero_stage=1, remat_policy="none")
    optimizer = make_optimizer("adamw")
    model = Model(cfg)

    def init_state(key):
        params = model.init(key)
        return {"params": params, "opt": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    pipe = SyntheticTokenPipeline(cfg, global_batch=batch, seq_len=seq,
                                  seed=seed)
    batches = [next(pipe) for _ in range(steps)]
    pipe.close()
    key = jax.random.PRNGKey(seed)

    mesh = make_mesh((2, 2), ("data", "model"))
    env = ShardingEnv(mesh)
    with activate(env):
        step, state_shardings = jit_sharded_train_step(cfg, run, optimizer, env)
        state = jax.jit(init_state, out_shardings=state_shardings)(key)
        sharded = []
        for b in batches:
            state, metrics = step(state, b)
            sharded.append(float(metrics["loss"]))
    held = {d.id: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        say(f"[sharded] device {d.id}: state {held[d.id] / 2**30:.3f} GiB; "
            f"memory_stats bytes_in_use "
            f"{stats.get('bytes_in_use', 'not reported')} peak "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    del state
    gc.collect()

    one_step = jax.jit(make_train_step(cfg, run, optimizer), donate_argnums=(0,))
    with jax.default_device(devices[0]):
        state = jax.jit(init_state)(key)
        single = []
        for b in batches:
            state, metrics = one_step(state, b)
            single.append(float(metrics["loss"]))
    del state
    gc.collect()

    diff = float(np.max(np.abs(np.subtract(sharded, single))))
    say(f"[sharded] {cfg.num_layers}-layer {cfg.name}, batch {batch}, seq "
        f"{seq}: 2x2 mesh losses {sharded}, one-chip losses {single}, max "
        f"|diff| {diff:.3e} (limit {LOSS_ATOL:.0e})")
    check(all(math.isfinite(x) for x in sharded + single), "non-finite loss")
    check(diff <= LOSS_ATOL, f"sharded and one-chip losses differ by {diff:.3e}")
    total = sum(held.values())
    check(max(held.values()) <= total / 2,
          f"train state is not spread over the mesh: {held}")


# ---------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train step vs one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    devices, hw = device_phase(args.chips)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    say(f"[setup] compile cache: {enable_compile_cache()}")
    # the simulator phase must not read a persistent cache, here or in the
    # sweep workers this process starts
    os.environ.pop("CHARON_CACHE_DIR", None)
    cfg = get_config(ARCH)
    cut = cfg.replace(num_layers=TRAIN["layers"])
    say(f"[setup] train cut: {cut.num_layers} of {cfg.num_layers} layers at "
        f"published widths, {cut.param_count() / 1e9:.3f} B params, of which "
        f"the tied embedding {cfg.vocab_size * cfg.d_model / 1e9:.3f} B")

    def timed(name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        gc.collect()
        say(f"[{name}] phase passed in {time.perf_counter() - t0:.1f} s")
        return out

    if args.chips == 4:
        timed("sharded", sharded_train_phase, cut, devices[:4],
              seed=args.seed, **SHARDED)
    else:
        served = timed("serve", serve_phase, cfg, seed=args.seed, **SERVE)
        trained = timed("train", train_phase, cut, batch=TRAIN["batch"],
                        seq=TRAIN["seq"], steps=TRAIN["steps"],
                        ckpt_dir=CKPT_DIR, seed=args.seed)
        warm = trained["step_s"][1:]
        timed("sim", sim_phase, hw, cfg, cut, slots=SERVE["slots"],
              cache_len=SERVE["cache_len"], batch=TRAIN["batch"],
              seq=TRAIN["seq"], decode_step_s=served["decode_step_s"],
              train_step_s=sum(warm) / len(warm))
    say(f"[done] all phases passed in {time.perf_counter() - t_start:.1f} s")
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAIL: {e}")
