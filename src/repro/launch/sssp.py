"""SSSP driver on the repro.api facade: solve on a generated graph
with any (ordering × EAGM variant × exchange) family member, verify
against Dijkstra, report work/sync metrics and cost-model time.

    PYTHONPATH=src python -m repro.launch.sssp --graph rmat1 --scale 14 \
        --spec delta:5+threadq/a2a
    # a composed per-level hierarchy (grammar v2):
    PYTHONPATH=src python -m repro.launch.sssp \
        --spec "delta:5 > pod:dijkstra > chunk:delta:1 /sparse"
    # batched query serving (one engine invocation for all sources):
    PYTHONPATH=src python -m repro.launch.sssp --sources 0 7 42
    # the family space at a glance:
    PYTHONPATH=src python -m repro.launch.sssp --list-variants

The old --root/--variant/--exchange flags still work and are folded
into the spec.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.engine import EXCHANGE_MODES


def build_graph(kind: str, scale: int, seed: int):
    from repro.graph import (
        grid_road_graph, rmat1, rmat2, small_world_graph,
    )

    if kind == "rmat1":
        return rmat1(scale, seed)
    if kind == "rmat2":
        return rmat2(scale, seed)
    if kind == "road":
        return grid_road_graph(int(2 ** (scale / 2)), seed)
    if kind == "smallworld":
        return small_world_graph(1 << scale, seed=seed)
    raise SystemExit(f"unknown graph kind {kind}")


#: example beyond-paper hierarchies shown by --list-variants
EXAMPLE_HIERARCHIES = [
    "delta:5 > pod:dijkstra",
    "delta:5 > pod:dijkstra > chunk:delta:1",
    "delta:7 > pod:delta:3 > chunk:topk:64",
    "chaotic > device:dijkstra > chunk:topk:32",
    "kla:2 > pod:dijkstra > device:dijkstra",
]


def list_variants_lines() -> list:
    """The preset (paper) grid plus example composed hierarchies, each
    with the collective scope realizing every annotation."""
    from repro.api import SolverConfig
    from repro.core import paper_variant_specs

    lines = ["preset grid (paper Figures 5-7, legacy grammar "
             "root+variant):"]
    for spec in paper_variant_specs():
        cfg = SolverConfig.from_spec(spec)
        lines.append(f"  {cfg.name:26s} {cfg.hierarchy.describe()}")
    lines.append("")
    lines.append("example composed hierarchies (grammar v2: "
                 "'root > level:ordering > ...[/exchange]'):")
    for spec in EXAMPLE_HIERARCHIES:
        cfg = SolverConfig.from_spec(spec)
        lines.append(f"  {spec:44s} {cfg.hierarchy.describe()}")
    lines.append("")
    lines.append("levels: global > pod > device > chunk; orderings: "
                 "chaotic | dijkstra | delta:D | kla:K | topk:B; "
                 "exchange: a2a | pmin | sparse | auto")
    return lines


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="rmat1",
                    choices=["rmat1", "rmat2", "road", "smallworld"])
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--spec", default=None,
                    help="solver spec: legacy 'root[+variant][/exchange]' "
                         "(e.g. delta:5+threadq/a2a) or a hierarchy "
                         "'root > level:ordering > ...[/exchange]' "
                         "(e.g. 'delta:5 > pod:dijkstra > chunk:delta:1"
                         "/sparse')")
    ap.add_argument("--list-variants", action="store_true",
                    help="enumerate the preset grid + example composed "
                         "hierarchies with their collective scopes, "
                         "then exit")
    ap.add_argument("--root", default="delta:5")
    ap.add_argument("--variant", default="buffer",
                    choices=["buffer", "threadq", "nodeq", "numaq"])
    ap.add_argument("--exchange", default="a2a",
                    choices=list(EXCHANGE_MODES))
    ap.add_argument("--partition", default=None, metavar="STRATEGY",
                    help="graph partitioner: block | shuffle[:seed] | "
                         "ebal | degree (also settable via the spec's "
                         "@segment, e.g. 'delta:5/sparse@ebal'; the "
                         "flag wins)")
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--sources", type=int, nargs="+", default=[0],
                    help=">1 source solves the batch in one engine call")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--problem", default="sssp",
                    choices=["sssp", "bfs", "cc", "sswp"],
                    help="processing function (all share the engine)")
    args = ap.parse_args()

    if args.list_variants:
        for line in list_variants_lines():
            print(line)
        return

    import jax

    from repro.api import (
        EveryVertex, Problem, SingleSource, Solver, SolverConfig,
    )
    from repro.core import dijkstra_reference, model_time_s
    from repro.launch.mesh import (
        compile_clock, make_local_topology, use_compile_cache,
    )

    use_compile_cache()
    g = build_graph(args.graph, args.scale, args.seed)
    topo = make_local_topology()

    spec = args.spec or f"{args.root}+{args.variant}/{args.exchange}"
    overrides = dict(chunk_size=args.chunk)
    if args.partition is not None:
        overrides["partition"] = args.partition
    cfg = SolverConfig.from_spec(spec, **overrides)
    solver = Solver(cfg, mesh=topo.mesh)
    pg = solver.partition(g)
    st = pg.load_stats()  # one scan, shared with the --verify printout
    print(f"[sssp] {pg.describe(st)}")
    if args.verify:
        print(f"[sssp] load balance ({pg.partitioner}): "
              f"rows/rank={st['rows_per_rank']} (padded to "
              f"{st['max_rows']}) edges/rank={st['edges_per_rank']}")
        print(f"[sssp] straggler ratio: rows={st['straggler_rows']:.3f} "
              f"edges={st['straggler_edges']:.3f} "
              f"ell_occupancy={st['ell_occupancy']:.3f}")

    if args.problem == "cc":
        if args.sources != [0]:
            print("[sssp] note: --sources is ignored for --problem cc "
                  "(CC seeds every vertex)")
        labels = ["all-vertices"]
        problems = [Problem(g, EveryVertex(), processing="cc")]
    else:
        labels = [f"source={v}" for v in args.sources]
        problems = [
            Problem(g, SingleSource(v), processing=args.problem)
            for v in args.sources
        ]

    with compile_clock() as cc:
        t0 = time.perf_counter()
        if cfg.adapt is not None and len(problems) > 1:
            # solve_batch rejects adaptive specs (one shared controller
            # schedule would steer every lane); solve them one at a time
            sols = [solver.solve(pb) for pb in problems]
        else:
            sols = solver.solve_batch(problems)
        # solutions are host arrays: the device work has finished
        wall = time.perf_counter() - t0
    print(f"[sssp] spec={cfg.name} batch={len(problems)}")
    for label, sol in zip(labels, sols):
        m = sol.metrics
        print(f"[sssp] {label} {m}")
        print(f"[sssp] cost_model(256 chips)={model_time_s(m, 256)*1e3:.2f}ms "
              f"reached={int(np.isfinite(sol.state).sum())}/{g.n}")
    dev = jax.devices()[0]
    print(f"[sssp] {dev.platform} ({dev.device_kind} x{jax.device_count()}): "
          f"wall={wall:.3f}s compile={cc.seconds:.3f}s "
          f"run={wall - cc.seconds:.3f}s")

    if args.verify and args.problem == "sssp":
        bad = 0
        for src, sol in zip(args.sources, sols):
            ref = dijkstra_reference(g, src)
            ok = np.allclose(
                np.where(np.isinf(ref), -1, ref),
                np.where(np.isinf(sol.state), -1, sol.state),
            )
            print(f"[sssp] source={src} verify vs Dijkstra: "
                  f"{'OK' if ok else 'MISMATCH'}")
            bad += 0 if ok else 1
        if bad:
            raise SystemExit(1)
    elif args.verify:
        print("[sssp] --verify oracle only wired for --problem sssp "
              "(BFS/CC/SSWP oracles live in tests/test_engine.py)")


if __name__ == "__main__":
    main()
