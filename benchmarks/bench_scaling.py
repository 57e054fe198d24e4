"""Paper §VI-A weak-scaling analogue: the same per-rank problem size
at P = 1, 2, 4, 8 ranks; reports how supersteps (sync) and exchanged
bytes grow with P per EAGM variant — the quantities whose balance
produces the paper's weak-scaling curves."""

from __future__ import annotations

import json
import os
import subprocess
import sys

CHILD = r"""
import json
import numpy as np, jax
from repro.graph import rmat2
from repro.api import Problem, SingleSource, Solver, SolverConfig
from repro.core import model_time_s

rows = []
for P, scale in [(1, 8), (2, 9), (4, 10), (8, 11)]:  # weak scaling
    g = rmat2(scale, seed=11)
    if P == 1:
        mesh = jax.make_mesh((1,), ("data",))
    elif P == 2:
        mesh = jax.make_mesh((2,), ("data",))
    elif P == 4:
        mesh = jax.make_mesh((2, 2), ("data", "model"))
    else:
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    for root, variant in [("delta:5", "buffer"), ("delta:5", "threadq"),
                          ("chaotic", "threadq"), ("kla:1", "nodeq")]:
        solver = Solver(
            SolverConfig(root=root, variant=variant, exchange="a2a",
                         chunk_size=256),
            mesh=mesh)
        sol = solver.solve(Problem(g, SingleSource(0)))
        rows.append(dict(P=P, scale=scale, root=root, variant=variant,
                         model_ms=model_time_s(sol.metrics, P) * 1e3,
                         **sol.metrics.as_dict()))
print(json.dumps(rows))
"""


def run() -> list:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # placeholder CPU devices: the child never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", CHILD], env=env,
                       capture_output=True, text=True, timeout=3000)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-3000:])
    return json.loads(r.stdout.splitlines()[-1])


def main() -> list[str]:
    out = []
    for r in run():
        name = (f"weakscale/P{r['P']}_s{r['scale']}/"
                f"{r['root']}+{r['variant']}")
        derived = (f"relax={r['relaxations']};steps={r['supersteps']};"
                   f"xbytes={r['exchange_bytes']}")
        out.append(f"{name},{r['model_ms']*1e3:.1f},{derived}")
    return out


if __name__ == "__main__":
    for line in main():
        print(line)
