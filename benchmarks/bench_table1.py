"""Paper Table I: real-world graphs × (Δ-stepping, KLA, Chaotic) ×
(buffer, threadq, nodeq, numaq).

The container has no network access, so each SNAP graph is replaced by
a stand-in with matching structural character (documented in
EXPERIMENTS.md): social graphs → small-world / R-MAT (low diameter,
skewed degrees); roadNet-CA → 2D grid (high diameter).  Per-graph
algorithm parameters follow the paper (e.g. Δ=1200 on the road
network, KLA K=10)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

CHILD = r"""
import json
import numpy as np, jax
from repro.graph import rmat1, small_world_graph, grid_road_graph
from repro.api import Problem, SingleSource, Solver, SolverConfig
from repro.core import dijkstra_reference, model_time_s

GRAPHS = [
    # (table-I stand-in, generator, AGM parameters)
    ("soc-live-proxy", small_world_graph(1 << 12, k=16, p=0.05, seed=1),
     [("delta:3", None), ("kla:1", None), ("chaotic", None)]),
    ("wiki-talk-proxy", rmat1(11, seed=3),
     [("delta:3", None), ("kla:1", None), ("chaotic", None)]),
    ("roadnet-proxy", grid_road_graph(64, seed=2),
     [("delta:1200", None), ("kla:10", None), ("chaotic", None)]),
    ("orkut-proxy", rmat1(11, seed=9, edge_factor=32),
     [("delta:10", None), ("kla:5", None), ("chaotic", None)]),
]
rows = []
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
for gname, g, algs in GRAPHS:
    ref = dijkstra_reference(g, 0)
    for root, _ in algs:
        for variant in ["buffer", "threadq", "nodeq", "numaq"]:
            solver = Solver(
                SolverConfig(root=root, variant=variant, exchange="a2a",
                             chunk_size=256),
                mesh=mesh)
            sol = solver.solve(Problem(g, SingleSource(0)))
            m = sol.metrics
            ok = np.allclose(np.where(np.isinf(ref), -1, ref),
                             np.where(np.isinf(sol.state), -1, sol.state))
            rows.append(dict(graph=gname, n=g.n, m=g.m, root=root,
                             variant=variant, ok=bool(ok),
                             model_ms=model_time_s(m, 64) * 1e3,
                             **m.as_dict()))
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
    rows = run()
    out = []
    for r in rows:
        assert r["ok"], r
        name = f"table1/{r['graph']}/{r['root']}+{r['variant']}"
        derived = (
            f"relax={r['relaxations']};steps={r['supersteps']};"
            f"commits={r['commits']};waste={r['relaxations']/max(1,r['commits']):.1f}"
        )
        out.append(f"{name},{r['model_ms']*1e3:.1f},{derived}")
    return out


if __name__ == "__main__":
    for line in main():
        print(line)
