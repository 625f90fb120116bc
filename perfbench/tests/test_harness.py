"""The harness finds its cells, configurations, traffic, limits and metric
readers by name, and refuses to measure without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_cells_are_listed_from_benchmark_json():
    assert harness.cells() == [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(name):
    c = harness.cell(name)
    model = harness.config(c["config"])
    assert (harness.HERE / "drivers" / f"{model['driver']}.py").exists()
    assert harness.traffic(c["traffic"])
    assert set(harness.limits(name)) > set()
    files = {cfg["name"]: cfg["file"] for cfg in BENCH["configs"]}
    assert (ROOT / files[c["config"]]).exists()
    assert Path(files[c["config"]]) == Path(
        "perfbench/configs") / f"{c['config']}.json"
    for trace in (False, True):
        assert harness.metrics_of(name, trace), (name, trace)


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(name):
    assert callable(harness.metric_reader(name))


def test_a_split_metric_takes_the_reader_of_its_quantity():
    for m in BENCH["per_layer"]:
        own = harness.HERE / "metrics" / f"{m['name']}.py"
        base = harness.HERE / "metrics" / f"{harness.quantity(m['name'])}.py"
        want = own if own.exists() else base
        assert harness.metric_reader(m["name"]).__code__.co_filename == \
            str(want)


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(ROOT / "perfbench/run.py"),
                           *args], cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=120)


def test_without_a_tpu_the_command_exits_nonzero_with_no_result():
    p = _run("--workload", BENCH["workloads"][0]["name"], "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == 1
    assert "no chip" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_unknown_workload_exits_nonzero():
    p = _run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode == 2
    assert not p.stdout.strip()
