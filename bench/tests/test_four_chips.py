"""The four-chip path, on four virtual CPU devices: a cell sharded over
four chips runs correct and its traced run reads the time spent in
collectives; with the exchange between chips left out, it is not
correct.  Each run is a process of its own, since JAX fixes the number
of devices when it starts."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).parent / "fixtures" / "newcell"
CPU_PEAKS = {"hbm_bytes_per_s": 100e9}


def no_exchange(x, axis_name, split_axis, concat_axis, tiled=False, **_):
    """``all_to_all`` with nothing sent between chips: each chip gets back
    the block it addressed to itself, and from every other chip nothing
    (+inf, the value of an empty slot)."""
    import jax
    import jax.numpy as jnp

    assert tiled and split_axis == 0 and concat_axis == 0
    me = jax.lax.axis_index(axis_name)
    parts = jax.lax.axis_size(axis_name)
    blocks = x.reshape(parts, -1, *x.shape[1:])
    own = (jnp.arange(parts) == me).reshape((parts,) + (1,) * (blocks.ndim - 1))
    return jnp.where(own, blocks, jnp.inf).reshape(x.shape)


@pytest.fixture(scope="module")
def cell_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("four") / "checkout"
    shutil.copytree(FIXTURE, root)
    return root


def run(root, trace, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, __file__, str(root), str(int(trace)), str(int(fault))],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_a_four_chip_cell_is_correct_and_reads_its_collectives(cell_root):
    r = run(cell_root, trace=True, fault=False)
    assert r["correct"] is True
    assert r["device"]["count"] == 4
    assert r["metrics"]["collective_ms_per_superstep"]["value"] > 0
    assert 0 < r["metrics"]["engine_roofline"]["value"] < 100


def test_without_the_exchange_between_chips_it_is_not_correct(cell_root):
    r = run(cell_root, trace=False, fault=True)
    assert r["correct"] is False
    assert r["checks"]["mismatched_vertices"]["value"] > 0


def main(root: str, trace: bool, fault: bool) -> None:
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness

    if fault:
        jax.lax.all_to_all = no_exchange  # traced into the engine's program
    r = harness.run(Path(root), "tiny.sparse.x4", 2**31 + 5, 0.3, trace,
                    time.perf_counter(), look_for_chip=False, peaks=CPU_PEAKS)
    print(json.dumps(r))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2] == "1", sys.argv[3] == "1")
