"""The check fails a broken timed path and the bfloat16 control, and
passes the program: one run for each fault the cell can have, with the
look for a chip skipped and the fault planted under the entry the
window drives."""

import io
import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import control, harness

FIXTURE = Path(__file__).parent / "fixtures" / "newcell"
CPU_PEAKS = {"hbm_bytes_per_s": 100e9}


@pytest.fixture(scope="module")
def cell_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("faults") / "checkout"
    shutil.copytree(FIXTURE, root)
    return root


class Broken:
    """The program's solver with ``fault`` applied to each answer where
    the solve produces it."""

    def __init__(self, solver, fault):
        self.solver, self.fault = solver, fault

    def solve(self, problem):
        sol = self.solver.solve(problem)
        ((key, _, _),) = problem.source_items()
        state = self.fault(np.array(sol.state), key)
        return SimpleNamespace(state=state, metrics=sol.metrics)


def unchanged(state, key):
    """The solve returns the state it started from."""
    start = np.full_like(state, np.inf)
    start[key] = 0
    return start


def altered(state, key):
    """One answer altered: the farthest vertex one unit off."""
    far = int(np.argmax(np.where(np.isfinite(state), state, -1)))
    state[far] += 1
    return state


def half_left_out(state, key):
    """Half of the vertices left out of the answer (never reached)."""
    state[1::2] = np.inf
    return state


def raises(state, key):
    raise RuntimeError("engine lost")


def run(root, fault):
    wrap = None if fault is None else (
        lambda solver, *graph: Broken(solver, fault))
    return harness.run(root, "tiny.sparse", 77, 0.2, False,
                       time.perf_counter(), look_for_chip=False,
                       peaks=CPU_PEAKS, wrap_solver=wrap)


def test_the_program_is_correct(cell_root):
    assert run(cell_root, None)["correct"] is True


@pytest.mark.parametrize("fault", [unchanged, altered, half_left_out, raises])
def test_a_broken_timed_path_is_not_correct(cell_root, fault):
    r = run(cell_root, fault)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_the_bf16_control_is_not_correct(cell_root):
    """On a graph whose distances pass 256 the control's values, returned
    as float32, fail the comparison; the program's do not."""
    out = io.StringIO()
    control.readings(cell_root, "tiny.heavy", 0.2, [5, 6], [7, 8],
                     look_for_chip=False, out=out)
    lines = [json.loads(x) for x in out.getvalue().splitlines()]
    assert [x["side"] for x in lines] == ["program"] * 2 + ["control"] * 2
    for x in lines:
        assert x["correct"] is (x["side"] == "program")
        assert (x["mismatched_vertices"] > 0) is (x["side"] == "control")
        assert x["failed_solves"] == 0
