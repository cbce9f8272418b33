"""The harness resolves every cell from files named in BENCHMARK.json, and
refuses to measure without a chip. Runs on the CPU; nothing here loads the
TPU library."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCHMARK = harness.load_benchmark()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_its_files_by_name(name):
    cell = harness.Cell(BENCHMARK, name)
    assert os.path.isfile(cell.entry_path)
    assert cell.traffic["entry"] == "streaming_init"
    assert cell.config["name"] == cell.workload["config"]
    assert "limits" in cell.config and cell.config["limits"]
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert hasattr(harness.load_module(cell.readers[m["name"]], "m"),
                       "read")
    assert hasattr(cell.entry(), "run")


def test_benchmark_file_keeps_to_its_shape():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        names.add(c["name"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    every = [n for b_ in (b["configs"], b["workloads"], b["end_to_end"],
                          b["per_layer"]) for n in (x["name"] for x in b_)]
    assert all(NAME.match(n) for n in every)
    assert len(json.dumps(b)) <= 64 * 1024


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0],
         "--seed", "2147483665", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(ROOT, {"PYTHONPATH": os.path.join(ROOT, "src")})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_a_checkout_with_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCHMARK["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
