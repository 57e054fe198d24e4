"""The share of the inter-chip roofline the collectives reach: the
bytes each collective sends, from a hand-written HLO snippet, and the
metric's reading on a four-chip cell run on four virtual CPU devices.
The run is a process of its own, since JAX fixes the number of devices
when it starts."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import collectives

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).parent / "fixtures" / "newcell"
#: a stand-in for the CPU's peaks: its memory and an interconnect
CPU_PEAKS = {"hbm_bytes_per_s": 100e9, "ici_bits_per_s": 800e9}

HLO = """\
HloModule jit_solve

%region_1.1 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %min.1 = s32[] minimum(%a, %b)
}

ENTRY %main.9 (p0: u32[4,1,1024], p1: s32[2]) -> u32[4,1,1024] {
  %p0 = u32[4,1,1024]{2,1,0:T(1,128)S(1)} parameter(0)
  %p1 = s32[2]{0:T(128)} parameter(1)
  %pmin.3 = s32[2]{0:T(128)S(1)} all-reduce(%p1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.1, metadata={op_name="jit(solve)/while/body/exchange/pmin"}
  %all-reduce.5 = (s32[]{:T(128)}, f32[8]{0}) all-reduce(%p1, %p1), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  %all-reduce-start.2 = f32[16]{0} all-reduce-start(%p1), channel_id=3, to_apply=%region_1.1
  %all-reduce-done.2 = f32[16]{0} all-reduce-done(%all-reduce-start.2)
  %add.7 = s32[2]{0} add(%pmin.3, %pmin.3)
  ROOT %all_to_all.12 = u32[4,1,1024]{2,1,0:T(1,128)S(1)} all-to-all(%p0), channel_id=4, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(solve)/while/body/exchange/cond/branch_1_fun/all_to_all"}
}
"""


def test_bytes_each_collective_sends_from_hlo_text():
    got = collectives.module_bytes(HLO, 4)
    assert got == {
        "pmin.3": 2 * 4 * 3 // 4,
        "all-reduce.5": (4 + 8 * 4) * 3 // 4,
        "all-reduce-start.2": 16 * 4 * 3 // 4,
        "all_to_all.12": 4 * 1024 * 4 * 3 // 4,  # three of four blocks
    }
    # a TPU trace's op name: the instruction with its type, no operands
    assert collectives.sent_bytes(
        "%all_to_all.12 = u32[4,1,1024] all-to-all", 4) == 12288
    assert collectives.sent_bytes("%add.7 = s32[2] add", 4) is None
    assert collectives.sent_bytes(
        "%all-reduce-done.2 = f32[16] all-reduce-done", 4) is None
    assert collectives.sent_bytes("all_to_all.12", 4) is None  # CPU: no type
    assert collectives.operand_bytes("(pred[3], bf16[2,2]{1,0})") == 11


@pytest.fixture(scope="module")
def cell_root(tmp_path_factory):
    """The fixture checkout, with the metric listed for its four-chip
    cell."""
    root = tmp_path_factory.mktemp("ici") / "checkout"
    shutil.copytree(FIXTURE, root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "exchange_ici_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "core.engine exchange",
        "moves": "teps", "workloads": ["tiny.sparse.x4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=2))
    return root


def test_a_four_chip_cell_reads_its_share_of_the_ici_roofline(cell_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__, str(cell_root)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.splitlines()[-1])
    assert r["correct"] is True
    assert 0 < r["metrics"]["exchange_ici_roofline"]["value"] <= 100
    assert r["metrics"]["collective_ms_per_superstep"]["value"] > 0


def main(root: str) -> None:
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, scopes

    scopes.ROOT = Path(root)  # the readers look for the run's trace there
    r = harness.run(Path(root), "tiny.sparse.x4", 2**31 + 7, 0.3, True,
                    time.perf_counter(), look_for_chip=False,
                    peaks=CPU_PEAKS)
    print(json.dumps(r))


if __name__ == "__main__":
    main(sys.argv[1])
