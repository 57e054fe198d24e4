"""A cell is data: the harness finds its configuration and traffic
files by name, and runs it end to end (here on the CPU, at a size a
test holds, with the look for a chip skipped)."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.drivers import closed

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).parent / "fixtures" / "newcell"
CPU_PEAKS = {"hbm_bytes_per_s": 100e9}


@pytest.fixture
def cell_root(tmp_path):
    """The fixture checkout: a BENCHMARK.json naming one new cell, and
    that cell's configuration and traffic files, nothing else."""
    root = tmp_path / "checkout"
    shutil.copytree(FIXTURE, root)
    return root


def run(root, trace, seconds=0.3, **kw):
    return harness.run(root, "tiny.sparse", 2**31 + 11, seconds, trace,
                       time.perf_counter(), look_for_chip=False,
                       peaks=CPU_PEAKS, **kw)


def test_cell_is_found_by_name(cell_root):
    cell = harness.load_cell(cell_root, "tiny.sparse")
    assert cell.chips == 1
    assert cell.config["name"] == "tiny-rmat1-s8"
    assert cell.config["scale"] == 8
    assert harness.driver(cell) is closed
    assert [m["name"] for m in cell.end_to_end] == [
        "teps", "device_gib", "setup_s"]
    assert "engine_roofline" in [m["name"] for m in cell.per_layer]
    with pytest.raises(KeyError):
        harness.load_cell(cell_root, "no-such-cell")


def test_the_shipped_cells_load():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert callable(harness.driver(cell).drive)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))


def test_untraced_run_reports_end_to_end_metrics(cell_root):
    r = run(cell_root, trace=False)
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] >= 1
    assert set(r["metrics"]) == {"teps", "device_gib", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["metrics"]["teps"]["unit"] == "edges/s"
    assert r["device"]["count"] >= 1
    assert r["checks"] == {
        "mismatched_vertices": {"value": 0, "limit": 0},
        "failed_solves": {"value": 0, "limit": 0},
    }
    # only the first run of a checkout generates the graph
    assert len(list((cell_root / harness.CACHE_DIR).glob("*.npz"))) == 1


def test_traced_run_reports_per_layer_metrics_and_a_breakdown(cell_root):
    r = run(cell_root, trace=True)
    assert r["correct"] is True
    assert set(r["metrics"]) == {
        "partition_s", "compile_s", "device_idle_pct", "supersteps",
        "superstep_ms", "engine_roofline"}
    assert 0 <= r["metrics"]["device_idle_pct"]["value"] < 100
    assert 0 < r["metrics"]["engine_roofline"]["value"] < 100
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert 0 < len(r["breakdown"]["device_ops"]) <= 10
    assert 0 < len(r["breakdown"]["idle_gaps"]) <= 10
    assert list(r)[-1] == "checks"


def test_a_mix_the_harness_cannot_drive_is_refused(cell_root):
    path = cell_root / "bench" / "traffic" / "tiny-closed.json"
    mix = json.loads(path.read_text())
    path.write_text(json.dumps(dict(mix, driver="no_such_driver")))
    with pytest.raises(ModuleNotFoundError):
        run(cell_root, trace=False)


class Stub:
    """A solver that answers at once, recording the keys it was asked."""

    def __init__(self):
        self.keys = []

    def solve(self, problem):
        ((key, _, _),) = problem.source_items()
        self.keys.append(key)
        return SimpleNamespace(
            state=np.zeros(1, np.float32),
            metrics=SimpleNamespace(supersteps=1, converged=True))


def test_a_window_keeps_every_answer():
    from repro.graph.formats import Graph

    ring = np.arange(8, dtype=np.int32)
    graph = Graph(8, ring, np.roll(ring, 1), np.ones(8, np.float32))
    s = SimpleNamespace(solver=Stub(), graph=graph)
    watch = SimpleNamespace(start=lambda: None, count=0)
    win = closed.drive(s, np.array([5, 6, 7]), 0.0, watch)
    assert [r.key for r in win.solves] == [5]  # the solve in progress
    win = closed.drive(s, np.array([5, 6, 7]), 0.05, watch)
    assert [r.key for r in win.solves][:3] == [5, 6, 7][:len(win.solves)]
    assert not any(r.error for r in win.solves)
    assert len(win.answers) == len(win.solves)
    assert all(a is not None for a in win.answers)


def cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rmat1-s20.sparse",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_command_exits_before_any_solve():
    p = cli(ROOT)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
