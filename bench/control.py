"""Readings that set the limits of a cell's check: the program's, over
many seeds, and the control's, the reference computed in bfloat16 and
put in the program's place.  Not part of a benchmark run.

    python3 bench/control.py --workload rmat2-s20.sparse --seconds 10 \
        --program-seeds 1 2 3 --control-seeds 4 5 6

One process sets the cell up once, then runs a window per seed, with
the program or with the control answering, and checks each as a run
does.  Each window prints one JSON line of its checks.
"""

import argparse
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]


class Bf16Control:
    """Stands in for the program's ``Solver``: answers each single-source
    problem with the reference's distances computed in bfloat16 and
    returned as float32."""

    def __init__(self, n, src, dst, w):
        self.graph = (n, src, dst, w)

    def solve(self, problem):
        from bench.reference import dijkstra_bf16

        ((key, _, _),) = problem.source_items()
        return SimpleNamespace(
            state=dijkstra_bf16(*self.graph, key),
            metrics=SimpleNamespace(supersteps=0, converged=True),
        )


def readings(root, workload, seconds, program_seeds, control_seeds,
             look_for_chip=True, out=sys.stdout):
    from bench import harness
    from bench.reference import Reference

    cell = harness.load_cell(root, workload)
    mix = harness.driver(cell)
    if look_for_chip:
        devices, _, _ = harness.find_chips(cell.chips)
    else:
        import jax

        devices = jax.devices()[:cell.chips]
    harness.use_compile_cache(root)
    watch = harness.CompileWatch()
    s = harness.set_up(cell, root, devices, watch, harness.Phases(0.0))
    program = s.solver
    control = Bf16Control(s.n, s.src, s.dst, s.w)
    ref = Reference(s.n, s.src, s.dst, s.w)
    for side, seeds, solver in (("program", program_seeds, program),
                                ("control", control_seeds, control)):
        for seed in seeds:
            s.solver = solver
            plan = mix.prepare(s, cell.traffic, seed)
            win = mix.drive(s, plan, seconds, watch)
            checks, _ = mix.check(s, win, ref)
            line = {"side": side, "seed": seed, "solves": len(win.solves),
                    "correct": all(c.ok for c in checks)}
            line.update({c.name: c.value for c in checks})
            print(json.dumps(line), file=out, flush=True)
    watch.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # not under /tmp
    from bench import harness

    try:
        readings(ROOT, args.workload, args.seconds, args.program_seeds,
                 args.control_seeds)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
