"""Paper Figures 5-7: Δ-stepping / KLA / Chaotic AGMs × EAGM variants
(buffer, threadq, nodeq, numaq) × candidate-exchange strategies
(dense a2a vs frontier-sparse vs auto) on RMAT1 and RMAT2.

The container cannot time a Cray, so each variant reports the
work/synchronization quantities its wall-clock decomposes into
(relaxations, commits, supersteps, actually-exchanged bytes) plus the
calibrated cost model over 256 chips (metrics.model_time_s) and the
measured wall time of one warm (compile-excluded) solve — reproducing
the *shape* of the paper's comparisons and tracking the sparse-
exchange win (per-superstep bytes scaling with the frontier capacity,
not |V|).  Runs on 8 placeholder devices in a subprocess so
pod/device/chunk-scoped orderings are distinct.

CLI:  PYTHONPATH=src python benchmarks/bench_variants.py \
          [--quick] [--scale N] [--json BENCH_variants.json] \
          [--json-partition BENCH_partition.json]

``--quick`` shrinks the grid (CI trajectory job); the JSON rows carry
supersteps, bytes, bytes/superstep, fallbacks and wall time per
variant × exchange so the perf trajectory accumulates across PRs.
Besides the preset grid, ``HIERARCHY_SPECS`` adds composed multi-level
hierarchy points (grammar v2, e.g. ``delta:5 > pod:dijkstra >
chunk:delta:1``) so the beyond-paper family space is tracked too —
including in ``--quick``.  ``--json-partition`` additionally runs the
partition dimension (``PARTITIONS``: relabeling partitioners on one
skewed RMAT at W=8, tracking the stacked row count R, straggler ratio
and exchanged bytes per strategy).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

EXCHANGES = ["a2a", "sparse", "auto"]

#: beyond-paper multi-level hierarchy family points (grammar v2) so
#: BENCH_variants.json tracks them alongside the preset grid
HIERARCHY_SPECS = [
    "delta:5 > pod:dijkstra > chunk:delta:1",
]

#: the partition dimension (BENCH_partition.json): relabeling
#: partitioners on one skewed RMAT under a fixed ordering, tracking
#: stacked row count R / straggler ratio / bytes / wall per strategy
PARTITIONS = ["block", "shuffle:7", "ebal", "degree"]

CHILD = r"""
import json, time
import numpy as np, jax
from repro.graph import rmat1, rmat2
from repro.api import Problem, SingleSource, Solver, SolverConfig
from repro.core import dijkstra_reference, model_time_s

SCALE = %(scale)d
QUICK = %(quick)d
rows = []
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
graphs = [("rmat1", rmat1)] if QUICK else [("rmat1", rmat1),
                                           ("rmat2", rmat2)]
if QUICK:
    roots = ["delta:5", "kla:2", "dijkstra", "chaotic"]
    variants = ["buffer", "threadq"]
else:
    roots = ["delta:3", "delta:5", "delta:7", "kla:1", "kla:2", "kla:3",
             "chaotic", "dijkstra"]
    variants = ["buffer", "threadq", "nodeq", "numaq"]
# (root, variant) preset points + composed multi-level hierarchies —
# a hierarchy config rides the same solve/measure path with
# variant='hierarchy' and the grammar-v2 spec as its root
points = [(root, variant) for root in roots for variant in variants]
points += [(spec, "hierarchy") for spec in %(hier_specs)s]
for gname, gen in graphs:
    g = gen(SCALE, seed=7)
    ref = dijkstra_reference(g, 0)
    for root, variant in points:
        for exchange in %(exchanges)s:
            if variant == "hierarchy":
                cfg = SolverConfig.from_spec(
                    root, exchange=exchange, chunk_size=256,
                    frontier_cap=%(frontier_cap)s)
            else:
                cfg = SolverConfig(root=root, variant=variant,
                                   exchange=exchange, chunk_size=256,
                                   frontier_cap=%(frontier_cap)s)
            solver = Solver(cfg, mesh=mesh)
            prob = Problem(g, SingleSource(0))
            sol = solver.solve(prob)          # compile + warm
            t0 = time.perf_counter()
            sol = solver.solve(prob)
            wall_s = time.perf_counter() - t0
            m = sol.metrics
            ok = np.allclose(np.where(np.isinf(ref), -1, ref),
                             np.where(np.isinf(sol.state), -1,
                                      sol.state))
            rows.append(dict(
                graph=gname, scale=SCALE, root=root, variant=variant,
                exchange=exchange, ok=bool(ok), wall_s=wall_s,
                model_ms=model_time_s(m, 256) * 1e3,
                bytes_per_superstep=(
                    m.exchange_bytes / max(1, m.supersteps)),
                **m.as_dict()))
print(json.dumps(rows))
"""


CHILD_PART = r"""
import json, time
import numpy as np, jax
from repro.graph import rmat1, partition_graph
from repro.api import Problem, SingleSource, Solver, SolverConfig
from repro.core import dijkstra_reference

SCALE = %(scale)d
WIDTH = 8  # narrow ELL => fat-row chunking dominates => skew visible
rows = []
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
g = rmat1(SCALE, seed=7)
ref = dijkstra_reference(g, 0)
for part in %(partitions)s:
    pg = partition_graph(g, 8, width=WIDTH, partitioner=part)
    st = pg.load_stats()
    for exchange in %(exchanges)s:
        cfg = SolverConfig.from_spec(
            "delta:5+threadq", exchange=exchange, chunk_size=256,
            partition=part, frontier_cap=%(frontier_cap)s)
        solver = Solver(cfg, mesh=mesh)
        prob = Problem(pg, SingleSource(0))
        sol = solver.solve(prob)          # compile + warm
        t0 = time.perf_counter()
        sol = solver.solve(prob)
        wall_s = time.perf_counter() - t0
        m = sol.metrics
        ok = np.allclose(np.where(np.isinf(ref), -1, ref),
                         np.where(np.isinf(sol.state), -1, sol.state))
        rows.append(dict(
            graph="rmat1", scale=SCALE, partition=part,
            exchange=exchange, ok=bool(ok), wall_s=wall_s,
            max_rows=st["max_rows"], n_local=pg.n_local,
            straggler_rows=st["straggler_rows"],
            ell_occupancy=st["ell_occupancy"],
            **m.as_dict()))
print(json.dumps(rows))
"""


CHILD_ADAPT = r"""
import json, time, warnings
import numpy as np, jax
from repro.graph import rmat1, grid_road_graph
from repro.api import Problem, SingleSource, Solver, SolverConfig
from repro.core import dijkstra_reference
from repro.tune import AutoTuner

SCALE = %(scale)d
QUICK = %(quick)d
rows = []
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
graphs = [("rmat1", rmat1(SCALE, seed=7)),
          ("road", grid_road_graph(int(2 ** (SCALE / 2)), 7))]
warnings.simplefilter("ignore", RuntimeWarning)
for gname, g in graphs:
    ref = dijkstra_reference(g, 0)
    # full delta grid even under --quick: the point of this cell is
    # that the tuner finds a better bucket width than the static
    # delta:5 baseline on the skewed family
    tuner = AutoTuner(
        mesh,
        orderings=("delta:3", "delta:5", "delta:10", "dijkstra"),
        partitions=("block",) if QUICK else ("block", "ebal"),
    )
    tuned = tuner.tune(g)
    points = [
        ("static", SolverConfig.from_spec("delta:5+buffer/a2a")),
        ("tuned", tuned),
        # adaptive controller from a deliberately tiny cap: rho must
        # grow it (retraces > 0) and retune delta mid-solve
        ("adaptive", SolverConfig.from_spec(
            "delta:5/sparse/adapt:rho", frontier_cap=4)),
    ]
    for kind, cfg in points:
        solver = Solver(cfg, mesh=mesh)
        prob = Problem(g, SingleSource(0))
        sol = solver.solve(prob)          # compile + warm
        t0 = time.perf_counter()
        sol = solver.solve(prob)
        wall_s = time.perf_counter() - t0
        m = sol.metrics
        ok = np.allclose(np.where(np.isinf(ref), -1, ref),
                         np.where(np.isinf(sol.state), -1, sol.state))
        rows.append(dict(
            graph=gname, scale=SCALE, kind=kind, spec=cfg.name,
            ok=bool(ok), wall_s=wall_s,
            bytes_per_superstep=(
                m.exchange_bytes / max(1, m.supersteps)),
            pilots=tuner.pilots_run, **m.as_dict()))
print(json.dumps(rows))
"""


CHILD_ROOFLINE = r"""
import json, time, warnings
import numpy as np, jax
from repro.graph import rmat1
from repro.api import Problem, SingleSource, Solver, SolverConfig
from repro.api.problem import get_processing
from repro.core import dijkstra_reference
from repro.roofline import superstep_profile

SCALE = %(scale)d
rows = []
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
g = rmat1(SCALE, seed=7)
ref = dijkstra_reference(g, 0)
warnings.simplefilter("ignore", RuntimeWarning)
base_state = None
base_metrics = None
for spec in ["delta:5/sparse", "delta:5/sparse/fused",
             "delta:5/sparse/q:bf16"]:
    cfg = SolverConfig.from_spec(spec, chunk_size=256)
    solver = Solver(cfg, mesh=mesh)
    prob = Problem(g, SingleSource(0))
    sol = solver.solve(prob)          # compile + warm
    t0 = time.perf_counter()
    sol = solver.solve(prob)
    wall_s = time.perf_counter() - t0
    m = sol.metrics
    ok = np.allclose(np.where(np.isinf(ref), -1, ref),
                     np.where(np.isinf(sol.state), -1, sol.state))
    assert ok, spec
    if base_state is None:
        base_state, base_metrics = np.asarray(sol.state), m.as_dict()
    else:
        # both the fused kernel and the quantized+repaired payload
        # must reproduce the exact baseline bit-for-bit
        assert np.array_equal(base_state, np.asarray(sol.state)), spec
    if spec == "delta:5/sparse/fused":
        assert m.as_dict() == base_metrics, (spec, m.as_dict())
    rows.append(dict(graph="rmat1", scale=SCALE, spec=spec,
                     ok=bool(ok), wall_s=wall_s,
                     bytes_per_superstep=(
                         m.exchange_bytes / max(1, m.supersteps)),
                     **m.as_dict()))
# the quantized payload must move strictly fewer bytes per superstep
assert (rows[2]["bytes_per_superstep"]
        < rows[0]["bytes_per_superstep"]), rows
# op-wise per-superstep roofline: fusion must cut HBM bytes
proc = get_processing("sssp")
prof = {}
for key, spec in [("unfused", "delta:5/sparse"),
                  ("fused", "delta:5/sparse/fused")]:
    ecfg = SolverConfig.from_spec(spec).engine_config(proc)
    prof[key] = superstep_profile(ecfg)
assert (prof["fused"]["hbm_bytes_per_superstep"]
        < prof["unfused"]["hbm_bytes_per_superstep"]), prof
print(json.dumps({"rows": rows, "roofline": prof, "ok": True}))
"""


def _run_child(child: str, timeout: int = 3000) -> list:
    """Run a benchmark child on 8 placeholder devices and parse its
    JSON rows (last stdout line)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # placeholder CPU devices: the child never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", child], env=env,
        capture_output=True, text=True, timeout=timeout,
    )
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-3000:])
    return json.loads(r.stdout.splitlines()[-1])


def run(
    scale: int = 10,
    quick: bool = False,
    exchanges=None,
    frontier_cap: int | None = 4,
) -> list:
    return _run_child(CHILD % {
        "scale": scale,
        "quick": int(quick),
        "exchanges": repr(exchanges or EXCHANGES),
        "frontier_cap": repr(frontier_cap),
        "hier_specs": repr(HIERARCHY_SPECS),
    })


def run_partition(
    scale: int = 10,
    partitions=None,
    exchanges=None,
    frontier_cap: int | None = 16,
) -> list:
    """The partition-dimension cell: one skewed RMAT, one ordering,
    every relabeling partitioner × {a2a, sparse}."""
    return _run_child(CHILD_PART % {
        "scale": scale,
        "partitions": repr(partitions or PARTITIONS),
        "exchanges": repr(exchanges or ["a2a", "sparse"]),
        "frontier_cap": repr(frontier_cap),
    })


def run_adaptive(scale: int = 10, quick: bool = False) -> list:
    """The autotune cell: static baseline vs offline-tuned spec vs
    runtime /adapt:rho controller on a skewed RMAT and a road grid."""
    return _run_child(CHILD_ADAPT % {
        "scale": scale,
        "quick": int(quick),
    })


def run_roofline(scale: int = 10) -> dict:
    """The kernel-fusion / quantized-exchange cell: exact sparse
    baseline vs '/fused' vs '/q:bf16' on one RMAT (bit-identity
    asserted in the child), plus the op-wise per-superstep HBM
    roofline for the unfused and fused programs."""
    return _run_child(CHILD_ROOFLINE % {"scale": scale})


def main_roofline(
    scale: int = 10, json_path: str | None = None
) -> list[str]:
    res = run_roofline(scale)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(res, f, indent=1, sort_keys=True)
    out = []
    for r in res["rows"]:
        name = f"roofline/{r['graph']}_s{r['scale']}/{r['spec']}"
        derived = (
            f"steps={r['supersteps']};xbytes={r['exchange_bytes']};"
            f"bps={r['bytes_per_superstep']:.0f};"
            f"repairs={r['repair_sweeps']}"
        )
        out.append(f"{name},{r['wall_s']*1e6:.1f},{derived}")
    pu = res["roofline"]["unfused"]["hbm_bytes_per_superstep"]
    pf = res["roofline"]["fused"]["hbm_bytes_per_superstep"]
    out.append(
        f"roofline/superstep_hbm_bytes,unfused={pu},fused={pf},"
        f"saved={pu - pf}"
    )
    return out


def main_adaptive(
    scale: int = 10,
    quick: bool = False,
    json_path: str | None = None,
) -> list[str]:
    rows = run_adaptive(scale, quick=quick)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
    out = []
    for r in rows:
        assert r["ok"], r
        name = f"autotune/{r['graph']}_s{r['scale']}/{r['kind']}"
        derived = (
            f"spec={r['spec']};steps={r['supersteps']};"
            f"bps={r['bytes_per_superstep']:.0f};"
            f"retraces={r['retraces']};fallbacks={r['sparse_fallbacks']}"
        )
        out.append(f"{name},{r['wall_s']*1e6:.1f},{derived}")
    return out


def main_partition(
    scale: int = 10, json_path: str | None = None
) -> list[str]:
    rows = run_partition(scale)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
    out = []
    for r in rows:
        assert r["ok"], r
        name = (
            f"partition/{r['graph']}_s{r['scale']}/"
            f"{r['partition']}/{r['exchange']}"
        )
        derived = (
            f"R={r['max_rows']};straggler={r['straggler_rows']:.3f};"
            f"steps={r['supersteps']};xbytes={r['exchange_bytes']};"
            f"relax={r['relaxations']}"
        )
        out.append(f"{name},{r['wall_s']*1e6:.1f},{derived}")
    return out


def main(
    scale: int = 10,
    quick: bool = False,
    json_path: str | None = None,
) -> list[str]:
    rows = run(scale, quick=quick)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
    out = []
    for r in rows:
        assert r["ok"], r
        if r["variant"] == "hierarchy":
            point = r["root"].replace(" ", "")  # grammar-v2 spec
            name = (
                f"family/{r['graph']}_s{r['scale']}/"
                f"{point}/{r['exchange']}"
            )
        else:
            name = (
                f"fig5-7/{r['graph']}_s{r['scale']}/"
                f"{r['root']}+{r['variant']}/{r['exchange']}"
            )
        derived = (
            f"relax={r['relaxations']};steps={r['supersteps']};"
            f"commits={r['commits']};xbytes={r['exchange_bytes']};"
            f"bps={r['bytes_per_superstep']:.0f};"
            f"fallbacks={r['sparse_fallbacks']}"
        )
        out.append(f"{name},{r['wall_s']*1e6:.1f},{derived}")
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small grid + scale 9 (CI trajectory job)")
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump the raw rows as JSON")
    ap.add_argument("--json-partition", default=None, metavar="PATH",
                    help="also run the partition-dimension cell "
                         "(block vs shuffle vs ebal vs degree on one "
                         "RMAT) and dump its rows as JSON")
    ap.add_argument("--adaptive", nargs="?", const="BENCH_autotune.json",
                    default=None, metavar="PATH",
                    help="run ONLY the autotune cell (static vs "
                         "offline-tuned vs /adapt:rho on rmat1 + road) "
                         "and dump its rows as JSON "
                         "(default PATH: %(const)s)")
    ap.add_argument("--roofline", nargs="?", const="BENCH_roofline.json",
                    default=None, metavar="PATH",
                    help="run ONLY the fusion/quantization cell "
                         "(exact sparse vs /fused vs /q:bf16 on rmat1, "
                         "bit-identity asserted, + per-superstep HBM "
                         "roofline) and dump it as JSON "
                         "(default PATH: %(const)s)")
    a = ap.parse_args()
    scale = a.scale if a.scale is not None else (9 if a.quick else 10)
    if a.roofline:
        for line in main_roofline(scale, json_path=a.roofline):
            print(line)
        sys.exit(0)
    if a.adaptive:
        for line in main_adaptive(scale, quick=a.quick,
                                  json_path=a.adaptive):
            print(line)
        sys.exit(0)
    for line in main(scale, quick=a.quick, json_path=a.json):
        print(line)
    if a.json_partition:
        for line in main_partition(scale, json_path=a.json_partition):
            print(line)
