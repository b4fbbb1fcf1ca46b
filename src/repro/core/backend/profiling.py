"""Profiling engine + profiling database (paper §3.3a).

Operators are synthesised from their IR description, executed under jit on
the locally available hardware (XLA-CPU in this container; the design is
identical for a GPU/TPU fleet — only the dispatch target changes), and the
measured latency is cached in a JSON database keyed by
(hardware, kind, dims, dtype).  The same database is the training set for the
prediction engine.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend.hardware import HardwareSpec
from repro.core.ir import OpNode

DB_PATH = Path(__file__).resolve().parents[4] / "results" / "profile_db.json"

_DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32, "f16": jnp.float16,
           "int8": jnp.int8, "f8": jnp.bfloat16}


def node_key(node: OpNode, hw_name: str) -> str:
    dims = node.attrs.get("mm_dims") or node.attrs.get("attn_dims") or node.out_shape
    return f"{hw_name}|{node.kind}|{','.join(map(str, dims))}|{node.dtype}"


class ProfileDB:
    """Measured latencies keyed by :func:`node_key`, loaded from ``path``;
    ``path=None`` keeps the database in memory and reads no file."""

    def __init__(self, path: Path | str | None = DB_PATH):
        self.path = Path(path) if path is not None else None
        self.data: dict[str, dict] = {}
        self.version = 0     # bumped on every put; price caches key on it
        if self.path is not None and self.path.exists():
            try:
                self.data = json.loads(self.path.read_text())
            except Exception:
                self.data = {}

    def get(self, key: str):
        e = self.data.get(key)
        return e["us"] if e else None

    def put(self, key: str, us: float, meta: dict):
        self.version += 1
        self.data[key] = {"us": us, **meta}

    def save(self):
        if self.path is None:
            raise ValueError("an in-memory ProfileDB has no file to save to")
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self.path.write_text(json.dumps(self.data, indent=0))

    def entries(self):
        return self.data.items()


_DISPATCH_US: list[float] = []


def dispatch_overhead_us() -> float:
    """Measured jit-dispatch floor on this host.  Profiled operator times
    subtract it: inside a fused step the dispatch is paid once per step, not
    per operator (calibrated like the paper's slowdown factors)."""
    if not _DISPATCH_US:
        # a minimal COMPUTE op (not identity): captures thread-pool wakeup +
        # buffer allocation, which every standalone op measurement pays
        x = jnp.zeros((8,), jnp.float32)
        f = jax.jit(lambda x: x + 1.0)
        jax.block_until_ready(f(x))
        ts = []
        for _ in range(80):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            ts.append(time.perf_counter() - t0)
        _DISPATCH_US.append(float(np.median(ts) * 1e6))
    return _DISPATCH_US[0]


def _time_fn(fn, *args, min_time_s: float = 0.05, max_iters: int = 200) -> float:
    """Median wall time per call (us) of a jitted fn, dispatch-corrected."""
    jfn = jax.jit(fn)
    out = jfn(*args)
    jax.block_until_ready(out)
    # warm
    jax.block_until_ready(jfn(*args))
    times = []
    total = 0.0
    while total < min_time_s and len(times) < max_iters:
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(*args))
        dt = time.perf_counter() - t0
        times.append(dt)
        total += dt
    raw = float(np.min(times) * 1e6)   # min: least contention noise
    return max(raw - dispatch_overhead_us(), 0.02 * raw)


def synthesize_and_measure(node: OpNode) -> float | None:
    """Build the operator from its IR description and time it on local XLA."""
    dt = _DTYPES.get(node.dtype, jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    k = node.kind
    try:
        if k == "matmul":
            dims = node.attrs.get("mm_dims")
            if not dims:
                return None
            m, n, kk = (int(x) for x in dims)
            a = jax.random.normal(rng, (m, kk), jnp.float32).astype(dt)
            b = jax.random.normal(rng, (kk, n), jnp.float32).astype(dt)
            return _time_fn(lambda x, y: x @ y, a, b)
        if k == "attention":
            bsz, h, sq, skv, d = (int(x) for x in node.attrs["attn_dims"])
            q = jax.random.normal(rng, (bsz, h, sq, d), jnp.float32).astype(dt)
            kv = jax.random.normal(rng, (bsz, h, skv, d), jnp.float32).astype(dt)

            def attn(q, kv):
                s = jnp.einsum("bhsd,bhtd->bhst", q, kv) / jnp.sqrt(float(d))
                p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
                return jnp.einsum("bhst,bhtd->bhsd", p, kv)

            return _time_fn(attn, q, kv)
        if k in ("norm", "softmax", "elementwise", "reduce", "copy", "transpose"):
            shape = tuple(int(x) for x in node.out_shape) or (1024,)
            x = jax.random.normal(rng, shape, jnp.float32).astype(dt)
            if k == "norm":
                w = jnp.ones(shape[-1:], dt)
                return _time_fn(
                    lambda x, w: (x * jax.lax.rsqrt(
                        jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True) + 1e-6
                    ).astype(x.dtype)) * w, x, w)
            if k == "softmax":
                return _time_fn(lambda x: jax.nn.softmax(x.astype(jnp.float32), -1).astype(x.dtype), x)
            if k == "reduce":
                return _time_fn(lambda x: jnp.sum(x.astype(jnp.float32)), x)
            if k == "transpose":
                if x.ndim < 2:
                    return _time_fn(lambda x: x + 1, x)
                perm = tuple(range(x.ndim - 2)) + (x.ndim - 1, x.ndim - 2)
                return _time_fn(lambda x: jnp.transpose(x, perm) + 0, x)
            return _time_fn(lambda x: jax.nn.silu(x) * x + 1.0, x)
        if k in ("embed", "gather"):
            v = int(node.attrs.get("vocab", 32768))
            d = int(node.out_shape[-1]) if node.out_shape else 512
            t = int(np.prod(node.out_shape[:-1])) if len(node.out_shape) > 1 else 1024
            tbl = jax.random.normal(rng, (v, d), jnp.float32).astype(dt)
            idx = jax.random.randint(rng, (t,), 0, v)
            return _time_fn(lambda tbl, idx: jnp.take(tbl, idx, axis=0), tbl, idx)
        return None
    except Exception:
        return None


class ProfilingEngine:
    """Highest-priority engine: exact measured latencies from the DB, with
    optional on-demand measurement on the local backend."""

    name = "profiling"
    priority = 30

    SUPPORTED = {"matmul", "attention", "norm", "softmax", "elementwise",
                 "reduce", "embed", "gather", "copy", "transpose"}

    def __init__(self, hw: HardwareSpec, db: ProfileDB | None = None,
                 *, measure_on_miss: bool = False):
        self.hw = hw
        self.db = db or ProfileDB()
        self.measure_on_miss = measure_on_miss and hw.name == "xla_cpu"
        self._self_puts = 0

    @property
    def state_version(self) -> int:
        """Changes when *external* DB mutation could alter an already-given
        answer (fused-engine price caches invalidate on it).  Own
        measure-on-miss puts are excluded: the value cached for that
        signature IS the measurement, so nothing previously answered
        changes."""
        return self.db.version - self._self_puts

    def supports(self, node: OpNode) -> bool:
        return node.kind in self.SUPPORTED

    def latency_us(self, node: OpNode) -> float | None:
        key = node_key(node, self.hw.name)
        us = self.db.get(key)
        if us is not None:
            return us
        if not self.measure_on_miss:
            return None
        us = synthesize_and_measure(node)
        if us is not None:
            self._self_puts += 1
            self.db.put(key, us, {"kind": node.kind,
                                  "dims": list(node.attrs.get("mm_dims")
                                               or node.attrs.get("attn_dims")
                                               or node.out_shape),
                                  "dtype": node.dtype,
                                  "flops": node.flops,
                                  "bytes": node.total_bytes})
        return us
