"""Every cell's configuration, traffic and metric readers are found by
name from ``BENCHMARK.json``; ``BENCHMARK.json`` keeps to the benchmark's
contract; and a run refuses to start without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_found_by_name(workload):
    cell = run.resolve(SPEC, workload)
    assert cell.conf["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == workload)
    assert cell.traffic["loop"] in ("open", "closed")
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        mod = run.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)
    for kind in ("models", "references"):
        assert (BENCH / kind / f"{cell.conf['model_type']}.py").exists()


def test_a_new_metric_is_a_new_file(tmp_path):
    path = tmp_path / "made_up.metric.py"
    path.write_text("def read(rec):\n    return 42.0\n")
    assert run.load_module(path).read(None) == 42.0


def test_benchmark_json_keeps_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({e["name"] for e in SPEC[k]}) == len(SPEC[k])
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).resolve().is_relative_to(BENCH)
        assert not any(k.endswith(("_dim", "_rank")) or k in (
            "hidden_size", "intermediate_size") for k in c["reduced"])


def _no_tpu_run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_without_a_tpu():
    out = _no_tpu_run(ROOT)
    assert out.returncode != 0 and out.stdout == ""
    assert "Nothing was run" in out.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _no_tpu_run(tmp_path)
    assert out.returncode != 0 and out.stdout == ""
