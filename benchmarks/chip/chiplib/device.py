"""The chip: refuse anything but a known TPU, point the compile cache into
the checkout, and read the device's numbers for the result line."""
from __future__ import annotations

import json
from pathlib import Path

import jax

from chiplib.registry import BENCH_DIR

# Fixed, inside the checkout: the directory is part of the cache's key, so a
# path made from a temporary name, a pid or the time would never hit.
CACHE_DIR = BENCH_DIR / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, too few chips, or a kind the peak table lacks."""


def peaks_for(kind: str, path: Path = BENCH_DIR / "peaks.json") -> dict:
    table = json.loads(Path(path).read_text())
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {path}: known {sorted(table)}")
    return table[kind]


def require_chips(n: int) -> tuple[list, dict]:
    """The first ``n`` TPU devices and their peaks; raises :class:`NoChip`."""
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoChip(f"JAX runs on {d0.platform!r}, not on a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n], peaks_for(d0.device_kind)


def enable_compile_cache(path: Path = CACHE_DIR) -> None:
    """Cache every program, the small eager ones too, so that a warm set-up
    compiles nothing.  This departs from the program's own
    ``enable_compile_cache``, which keeps JAX's floor of 1 s of compile time:
    under it a program that compiles faster is compiled anew in every
    process.  The serving engine re-lowers its eager prefill on every
    admission, so here each admission inside the window loads that program
    from this cache (the ``[window]`` line counts the loads and the compile
    time each stood for)."""
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``, 0 where the backend
    keeps no count."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def describe(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak_bytes(devices)}


class CompileCounter:
    """Programs JAX compiled or loaded from the persistent cache while
    ``on``, and for each load the compile time it stood for.

    JAX reports ``backend_compile_duration`` once per program, whether it
    compiled it or loaded it; a load also reports its retrieval time, after
    the compile time it saved."""

    PROGRAM = "/jax/core/compile/backend_compile_duration"
    SAVED = "/jax/compilation_cache/compile_time_saved_sec"
    LOADED = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        self.on = False
        self.programs = 0
        self.load_compile_s: list[float] = []   # per load: saved + retrieval
        self._saved = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_kw):
        if not self.on:
            return
        if name == self.PROGRAM:
            self.programs += 1
        elif name == self.SAVED:
            self._saved = secs
        elif name == self.LOADED:
            self.load_compile_s.append(self._saved + secs)

    def summary(self) -> str:
        n, loads = self.programs, sorted(self.load_compile_s)
        text = (f"{n} programs inside the window: {len(loads)} loaded from the "
                f"compile cache, {n - len(loads)} compiled")
        if loads:   # JAX stores a compile time in whole seconds, rounded down
            text += (f"; the loaded ones had compiled in {loads[len(loads) // 2]:.0f} s "
                     f"(median), {loads[-1]:.0f} s (max), whole seconds rounded down; "
                     f"{sum(x < 1.0 for x in loads)} of them under 1 s")
        return text
