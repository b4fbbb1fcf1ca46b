"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

Each part is a file of its own, so a later change adds a cell, a
configuration, a traffic mix or a metric by adding files and entries and
never edits one that is there:

* ``configs/<config>.json``  — one model configuration;
* ``traffic/<traffic>.json`` — one traffic mix, naming its ``driver``;
* ``drivers/<driver>.py``    — one driver per kind of traffic, ``run(ctx)``;
* ``metrics/<family>.py``    — one reader per per-layer metric family
  (the name before the first ``.``), ``read(run) -> float | None``;
* ``limits/<workload>.json`` — the limit of each number a cell compares;
* ``refs/<reference>.py``    — a configuration's plain reference;
* ``systems/<system>.py``    — how the system under test is built for a
  configuration.

A configuration names its reference and its system apart: the reference is
the architecture's plain math, the system how one path of the program is
built on the reference's weights.  A later path of the same program (a
paged engine, the Pallas kernels) adds a system and keeps the reference.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


class Registry:
    """The benchmark's entries, resolved against the files under ``root``."""

    def __init__(self, bench_json: Path, root: Path = BENCH_DIR):
        self.root = Path(root)
        self.bench = json.loads(Path(bench_json).read_text())
        self._modules: dict[tuple[str, str], object] = {}

    # ---- entries of BENCHMARK.json
    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.bench["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {known}")

    def metrics(self, kind: str, workload: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics that ``workload``
        reports: those that list it, and those that list no cells."""
        return [m for m in self.bench[kind]
                if workload in m.get("workloads", [workload])]

    # ---- files
    def _json(self, kind: str, name: str) -> dict:
        path = self.root / kind / f"{name}.json"
        if not path.is_file():
            raise KeyError(f"no {kind} file {path}")
        return json.loads(path.read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, workload: str) -> dict:
        return self._json("limits", workload)

    def module(self, kind: str, name: str):
        """Load ``<root>/<kind>/<name>.py`` once, under a name of its own."""
        key = (kind, name)
        if key not in self._modules:
            path = self.root / kind / f"{name}.py"
            if not path.is_file():
                raise KeyError(f"no {kind} module {path}")
            mod_name = f"chipbench_{kind}_{name}".replace("-", "_").replace(".", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def driver(self, traffic: dict):
        return self.module("drivers", traffic["driver"])

    def reader(self, metric_name: str):
        return self.module("metrics", metric_name.split(".", 1)[0])
