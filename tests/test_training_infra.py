"""Checkpointing (incl. restart + retention), data pipeline determinism,
optimizers, fault-tolerance supervision, sharding resolver."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.distributed.sharding import (
    DEFAULT_RULES, ShardingEnv, activate, fsdp_spec, resolve_spec,
)
from repro.training.checkpoint import CheckpointManager
from repro.training.data import SyntheticTokenPipeline
from repro.training.fault_tolerance import ElasticPlan, StepMonitor, run_with_restarts
from repro.training.optimizer import (
    adafactor, adamw, cosine_schedule, int8_compress_decompress, make_optimizer,
)


# ---------------- optimizers ----------------

def test_adamw_matches_manual_first_step():
    lr = lambda step: jnp.asarray(0.1)
    opt = adamw(lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0)
    p = {"w": jnp.asarray([1.0, 2.0])}
    g = {"w": jnp.asarray([0.5, -0.5])}
    st = opt.init(p)
    new_p, st = opt.update(g, st, p)
    # bias-corrected first step = -lr * g/|g| elementwise (adam property)
    np.testing.assert_allclose(new_p["w"], [1.0 - 0.1, 2.0 + 0.1], rtol=1e-4)


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_descends_quadratic(name):
    opt = make_optimizer(name, peak_lr=0.05)
    p = {"w": jnp.ones((8, 8))}
    st = opt.init(p)
    loss = lambda p: jnp.sum(jnp.square(p["w"]))
    l0 = float(loss(p))
    for _ in range(60):
        g = jax.grad(loss)(p)
        p, st = opt.update(g, st, p)
    assert float(loss(p)) < l0 * 0.7


def test_adafactor_state_is_factored():
    opt = make_optimizer("adafactor")
    p = {"w": jnp.ones((64, 32))}
    st = opt.init(p)
    sizes = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(st["f"]))
    assert sizes == 64 + 32  # vr + vc, not 64*32


def test_int8_compression_bounded_error():
    g = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    gq = int8_compress_decompress(g)
    assert float(jnp.max(jnp.abs(g - gq))) <= float(jnp.max(jnp.abs(g))) / 127 + 1e-6


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup=10, total=100)
    assert float(lr(jnp.asarray(0))) == 0.0
    assert float(lr(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(lr(jnp.asarray(100))) == pytest.approx(0.1, rel=1e-2)


# ---------------- checkpoint ----------------

def test_checkpoint_roundtrip_and_retention(tmp_path):
    ckpt = CheckpointManager(tmp_path, keep=2)
    state = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
             "step": jnp.asarray(7, jnp.int32)}
    for s in (1, 2, 3):
        ckpt.save(s, state, extra={"data_step": s * 10})
    assert ckpt.all_steps() == [2, 3]  # retention
    target = jax.tree.map(jnp.zeros_like, state)
    restored, extra = ckpt.restore(target)
    assert extra["data_step"] == 30
    np.testing.assert_array_equal(restored["a"], state["a"])
    assert restored["nested"]["b"].dtype == jnp.bfloat16


def test_checkpoint_restart_resumes_stream(tmp_path):
    cfg = get_tiny_config("xlstm-125m")
    pipe = SyntheticTokenPipeline(cfg, global_batch=2, seq_len=8, seed=3)
    b0, b1, b2 = next(pipe), next(pipe), next(pipe)
    pipe.close()
    pipe2 = SyntheticTokenPipeline(cfg, global_batch=2, seq_len=8, seed=3,
                                   start_step=2)
    b2b = next(pipe2)
    pipe2.close()
    np.testing.assert_array_equal(b2["tokens"], b2b["tokens"])


def test_run_with_restarts_recovers(tmp_path):
    ckpt = CheckpointManager(tmp_path, keep=2)
    calls = []

    def loop(start):
        calls.append(start)
        if len(calls) == 1:
            ckpt.save(4, {"x": jnp.ones(())})
            raise RuntimeError("simulated node failure")
        return 10

    assert run_with_restarts(loop, ckpt, max_restarts=2) == 10
    assert calls == [0, 5]  # restarted after the step-4 checkpoint


def test_elastic_plan_rescale():
    plan = ElasticPlan(tp=4, pp=2, dp=8, global_batch=64)
    new = plan.rescale(surviving_chips=48)  # lost 16 of 64
    assert new.tp == 4 and new.pp == 2
    assert new.dp == 6 and new.global_batch == 48


def test_elastic_plan_rescale_batch_accounting():
    plan = ElasticPlan(tp=2, pp=2, dp=4, global_batch=32)
    per_dp = plan.global_batch // plan.dp
    for chips in (16, 12, 8, 5, 3):
        new = plan.rescale(chips)
        assert new.dp == max(chips // 4, 1)
        # per-replica batch is preserved exactly; global batch follows dp
        assert new.global_batch == per_dp * new.dp
        assert new.global_batch % new.dp == 0
    # even losing everything but one chip leaves a runnable dp=1 plan
    assert plan.rescale(1).dp == 1


def test_step_monitor_stop_before_start_raises():
    mon = StepMonitor()
    with pytest.raises(RuntimeError, match="before start"):
        mon.stop()
    # and stop() consumes the start: a second stop needs a fresh start
    mon.start()
    mon.stop()
    with pytest.raises(RuntimeError, match="before start"):
        mon.stop()


def test_run_with_restarts_budget_resets_on_progress(tmp_path):
    # 4 transient failures, each after a *new* checkpoint: with
    # max_restarts=2 an absolute budget would raise on the 3rd, but the
    # progress-aware budget keeps going because every attempt advanced.
    ckpt = CheckpointManager(tmp_path, keep=10)
    calls = []

    def loop(start):
        calls.append(start)
        if len(calls) <= 4:
            ckpt.save(len(calls) * 10, {"x": jnp.ones(())})
            raise RuntimeError("transient fault")
        return 99

    assert run_with_restarts(loop, ckpt, max_restarts=2) == 99
    assert calls == [0, 11, 21, 31, 41]


def test_run_with_restarts_crash_loop_still_raises(tmp_path):
    # No checkpoint progress between failures -> the budget is NOT reset
    # and the loop gives up after max_restarts retries.
    ckpt = CheckpointManager(tmp_path)
    calls = []

    def loop(start):
        calls.append(start)
        raise RuntimeError("persistent fault")

    with pytest.raises(RuntimeError, match="persistent"):
        run_with_restarts(loop, ckpt, max_restarts=2)
    assert calls == [0, 0, 0]  # initial try + 2 retries


def test_checkpoint_restore_rejects_dtype_mismatch(tmp_path):
    ckpt = CheckpointManager(tmp_path)
    ckpt.save(1, {"w": jnp.ones((2, 2), jnp.float32)})
    target = {"w": jnp.zeros((2, 2), jnp.int32)}
    with pytest.raises(ValueError, match="dtype mismatch"):
        ckpt.restore(target)
    # bf16 target vs float32 on disk is the save-widening round trip, OK
    ckpt.save(2, {"b": jnp.ones((3,), jnp.bfloat16)})
    restored, _ = ckpt.restore({"b": jnp.zeros((3,), jnp.bfloat16)}, step=2)
    assert restored["b"].dtype == jnp.bfloat16


def test_checkpoint_ignores_leftover_tmp_dir(tmp_path):
    # a crash mid-write leaves .tmp_step_*; it must be invisible to
    # all_steps()/latest_step() and a later save of that step must succeed
    ckpt = CheckpointManager(tmp_path)
    ckpt.save(3, {"x": jnp.ones(())})
    crashed = tmp_path / ".tmp_step_000000007"
    crashed.mkdir()
    (crashed / "arrays.npz").write_bytes(b"garbage")
    assert ckpt.all_steps() == [3]
    assert ckpt.latest_step() == 3
    ckpt.save(7, {"x": jnp.full((), 2.0)})   # reuses + replaces the tmp dir
    assert ckpt.all_steps() == [3, 7]
    restored, _ = ckpt.restore({"x": jnp.zeros(())}, step=7)
    assert float(restored["x"]) == 2.0


def test_checkpoint_async_wait_ordering(tmp_path):
    ckpt = CheckpointManager(tmp_path, keep=10, async_save=True)
    state = {"x": jnp.arange(4, dtype=jnp.float32)}
    # back-to-back async saves: each save waits for the previous writer,
    # so publishes land in order and wait() makes the last one durable
    for s in (1, 2, 3):
        ckpt.save(s, {"x": jnp.full((4,), float(s))})
    ckpt.wait()
    assert ckpt.all_steps() == [1, 2, 3]
    restored, _ = ckpt.restore(state)
    np.testing.assert_array_equal(restored["x"], np.full((4,), 3.0))


def test_checkpoint_resharding_restore_roundtrip(tmp_path):
    ckpt = CheckpointManager(tmp_path)
    state = {"w": jnp.arange(8, dtype=jnp.float32).reshape(2, 4)}
    ckpt.save(1, state)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = {"w": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
    restored, _ = ckpt.restore(jax.tree.map(jnp.zeros_like, state),
                               shardings=sh)
    np.testing.assert_array_equal(restored["w"], state["w"])
    assert restored["w"].sharding.is_equivalent_to(sh["w"], 2)


def test_step_monitor_detects_straggler():
    mon = StepMonitor(window=50, z_threshold=2.0)
    import time as _t
    for i in range(12):
        mon.start()
        _t.sleep(0.001)
        mon.stop()
    mon.start()
    _t.sleep(0.08)
    mon.stop()
    assert mon.stragglers


# ---------------- sharding resolver ----------------

def _env(shape=(4, 2), axes=("data", "model")):
    # AbstractMesh: the resolver only needs axis names/sizes (1-device CI)
    mesh = jax.sharding.AbstractMesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    return ShardingEnv(mesh)


def test_resolver_divisibility_fallback():
    env = _env()
    # 6 heads on a 2-wide model axis: shardable; 7: dropped
    spec = resolve_spec(env, ("batch", "kv_heads"), (8, 6))
    assert spec == jax.sharding.PartitionSpec(("data",), "model") or \
        spec == jax.sharding.PartitionSpec("data", "model")
    spec2 = resolve_spec(env, ("batch", "kv_heads"), (8, 7))
    assert len(spec2) == 1  # model axis dropped


def test_resolver_no_axis_reuse():
    env = _env()
    spec = resolve_spec(env, ("heads", "ffn"), (4, 4))  # both want 'model'
    used = [s for s in spec if s is not None]
    assert used.count("model") <= 1


def test_fsdp_spec_adds_data_axis():
    env = _env()
    spec = fsdp_spec(env, ("layer", None, "ffn"), (3, 8, 4), skip_leading=1)
    # dim1 (=8) divisible by data(4): gets the fsdp axis
    assert spec[1] == "data" or spec[1] == ("data",)
