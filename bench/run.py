"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload rmat1-s20.sparse --seed 7 --seconds 30 --trace 0

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` records a profiler trace of the window and reports the
per-layer metrics, the device's busy time and a breakdown.  The last
line of stdout is the JSON result; the last lines of stderr are the
numbers checked against the reference, each beside its limit.  Exits 3
without a result when JAX finds no TPU, too few chips or a chip whose
peaks are unknown, and 2 when the checkout lacks the program.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    # the checkout and its program in place of this script's directory,
    # whose module names would shadow installed ones
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # not under /tmp
    from bench import harness

    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
