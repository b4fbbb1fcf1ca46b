"""chip_smoke.py rehearsed on the CPU at tiny sizes.

Each phase runs the code the chip run runs; only the config is cut.  The
script itself must refuse a machine without a TPU.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_tiny_config
from repro.core.backend.hardware import TPU_V5E

REPO = Path(__file__).resolve().parents[1]
TINY = get_tiny_config("phi4-mini-3.8b")


@pytest.fixture(scope="module", autouse=True)
def no_repo_compile_cache(tmp_path_factory):
    """With the variable set, the entry points leave jax's cache config
    alone: these tests write no cache into the repo and change no global
    config for later tests in the process."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax_cache")))
        yield


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_serve_phase(smoke):
    out = smoke.serve_phase(TINY, requests=3, min_len=4, max_len=24,
                            max_new=4, slots=2, cache_len=64, seed=0)
    assert out["decode_step_s"] > 0


def test_train_phase_starts_fresh(smoke, tmp_path):
    kw = dict(batch=2, seq=16, steps=3, ckpt_dir=tmp_path / "ckpt", seed=0)
    first = smoke.train_phase(TINY, **kw)
    again = smoke.train_phase(TINY, **kw)   # the kept checkpoint is cleared
    assert first["steps_run"] == again["steps_run"] == 3
    assert first["loss"] == again["loss"]


def test_train_reports_a_stale_checkpoint(smoke, tmp_path):
    from repro.launch import train as train_cli
    argv = ["--steps", "2", "--batch", "2", "--seq", "16", "--ckpt-every",
            "2", "--ckpt-dir", str(tmp_path)]
    assert train_cli.main(argv, cfg=TINY)["steps_run"] == 2
    stale = train_cli.main(argv, cfg=TINY)
    assert stale["steps_run"] == 0 and stale["saved_step"] == 1


def test_sim_phase(smoke):
    smoke.sim_phase(TPU_V5E, TINY, TINY, slots=2, cache_len=64, batch=2,
                    seq=16, decode_step_s=1e-3, train_step_s=1e-3)


def test_sharded_train_phase_on_four_cpu_devices():
    """The --chips 4 path on 4 virtual devices (subprocess: the device count
    is fixed when jax's backend starts)."""
    code = f"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {str(REPO / "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec); spec.loader.exec_module(smoke)
from repro.configs import get_tiny_config
smoke.sharded_train_phase(get_tiny_config("phi4-mini-3.8b"), jax.devices(),
                          batch=4, seq=16, steps=3, seed=0)
print("SHARDED_OK")
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
