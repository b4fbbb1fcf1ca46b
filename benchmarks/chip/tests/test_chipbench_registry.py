"""The registry finds every part by name, and a configuration, a mix, a
limit file and a metric reader added as new files need no edit to any
file that is there."""
from __future__ import annotations

import hashlib
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench_cpu import BENCH, REPO, tiny_tree
from chiplib.registry import Registry

BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def digest(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_every_cell_resolves():
    reg = Registry(REPO / "BENCHMARK.json")
    for cell in BENCHMARK["workloads"]:
        conf, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
        assert reg.driver(mix).run and reg.limits(cell["name"])
        assert reg.module("refs", conf["reference"]) and reg.module("systems", conf["system"])
        for kind in ("end_to_end", "per_layer"):
            for m in reg.metrics(kind, cell["name"]):
                if kind == "per_layer":
                    assert reg.reader(m["name"]).read


def test_added_files_need_no_edit(tmp_path):
    root = tiny_tree(tmp_path)
    before = digest(root)
    conf = json.loads((root / "configs" / "phi4-mini-3.8b.json").read_text())
    (root / "configs" / "phi4-mini-3.8b-half.json").write_text(
        json.dumps(dict(conf, config=dict(conf["config"], num_hidden_layers=1))))
    (root / "traffic" / "burst.json").write_text(json.dumps({"driver": "open_loop_serve"}))
    (root / "limits" / "phi4-burst.json").write_text(json.dumps({"logit_gap": 0.5}))
    (root / "metrics" / "queue_len.py").write_text("def read(run):\n    return 7.0\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "phi4-burst", "config": "phi4-mini-3.8b-half",
                               "traffic": "burst", "chips": 1, "why": "bursts"})
    bench["per_layer"].append({"name": "queue_len.burst", "unit": "1", "better": "lower",
                               "source": "host_clock", "layer": "serving engine admission",
                               "moves": "ttft_p90_ms", "workloads": ["phi4-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    reg = Registry(tmp_path / "BENCHMARK.json", root)
    cell = reg.workload("phi4-burst")
    assert reg.config(cell["config"])["config"]["num_hidden_layers"] == 1
    assert reg.driver(reg.traffic(cell["traffic"])).__name__.endswith("open_loop_serve")
    assert reg.limits("phi4-burst") == {"logit_gap": 0.5}
    assert [m["name"] for m in reg.metrics("per_layer", "phi4-burst")] == ["queue_len.burst"]
    assert reg.reader("queue_len.burst").read(None) == 7.0
    assert {p: h for p, h in digest(root).items() if p in before} == before


def test_benchmark_json_keeps_to_its_shape():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs", "workloads",
                              "end_to_end", "per_layer"}
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    names = ([c["name"] for c in BENCHMARK["configs"]] + [w["name"] for w in BENCHMARK["workloads"]]
             + [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]])
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file()
        assert c["reduced"] == json.loads((REPO / c["file"]).read_text())["reduced"]
    for w in BENCHMARK["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and len(w["why"]) <= 200
        reported = [m for m in BENCHMARK["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
    for m in BENCHMARK["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        for w in m["workloads"]:
            moved = next(x for x in BENCHMARK["end_to_end"] if x["name"] == m["moves"])
            assert w in moved.get("workloads", [w])


@pytest.mark.parametrize("where", ["repo", "bench_only"])
def test_run_refuses_without_a_chip_or_without_the_program(tmp_path, where):
    """No TPU (here), or a directory holding only BENCHMARK.json and the
    benchmark: non-zero exit and no result line."""
    if where == "repo":
        cmd, cwd = [sys.executable, str(BENCH / "run.py")], REPO
    else:
        root = tiny_tree(tmp_path)
        (tmp_path / "benchmarks").mkdir()
        root.rename(tmp_path / "benchmarks" / "chip")
        cmd, cwd = [sys.executable, "benchmarks/chip/run.py"], tmp_path
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(cmd + ["--workload", "phi4-chat", "--seed", "1", "--seconds", "1",
                              "--trace", "0"], cwd=cwd, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
