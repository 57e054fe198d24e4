"""Chip smoke test: the SSSP solve and route-service path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the distributed exchanges, 4 chips

One chip: generates Graph500's Kronecker graph ``rmat1(20)`` (1 M
vertices, 31 M edges) and drives it through the normal entry points.
``Solver`` solves it from two sources on the ``a2a`` exchange and from
four on ``sparse``; both are checked against the host Dijkstra and
against each other bit for bit.  A ``LandmarkIndex`` over the four
sources is one ``solve_batch``, whose lanes must match the single
solves.  A ``Router`` answers a mixed query stream, then an
``UpdateFeed`` edge update must leave a refreshed cache entry
bit-identical to a cold solve.

Four chips: ``rmat1(20)`` partitioned over a 4-chip mesh, solved with
the ``a2a``, ``sparse`` and ``pmin`` exchanges from one source, each
checked against Dijkstra and against each other.  Scale 20 is the
smallest at which a sparse payload that carried its indices as f32
bits answered wrong on four chips (scale 19 passed).

Each phase prints its compile and run seconds, the device's peak bytes
in use, and the engine trace count.  Any failed check exits non-zero.
Without a TPU the script exits non-zero before solving anything.  The
last line of stdout is the JSON result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

#: Graph500 scales.  One chip holds scale 20 with room for the batched
#: engine's temporaries.  Four chips would hold scale 22 (the same
#: graph per chip), at about four times the generation, Dijkstra and
#: solve time of the scale-20 run kept here to bound a 4-chip call;
#: scale 20 is the smallest at which a lossy sparse exchange showed.
SCALE_ONE_CHIP = 20
SCALE_FOUR_CHIPS = 20
SPEC_A2A = "delta:5+buffer/a2a"
SPEC_SPARSE = "delta:5+buffer/sparse"
SPEC_PMIN = "delta:5+buffer/pmin"


def require(ok, what: str) -> None:
    """A check that ``python -O`` keeps."""
    if not ok:
        raise AssertionError(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return a.shape == b.shape and np.array_equal(
        a.view(np.uint32), b.view(np.uint32)
    )


def device_report() -> str:
    from repro.api import trace_count

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", "n/a"))
    return f"peak_bytes_in_use={peaks} engine_traces={trace_count()}"


def timed(fn):
    """Run ``fn`` (whose results are host arrays, so the device work has
    finished when it returns); return (result, compile_s, run_s)."""
    from repro.launch.mesh import compile_clock

    with compile_clock() as cc:
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
    return out, cc.seconds, wall - cc.seconds


def pick_sources(g, count: int, seed: int) -> list[int]:
    """Graph500's search keys: distinct vertices with an edge, drawn
    from a seed."""
    deg = np.bincount(g.src, minlength=g.n)
    cand = np.flatnonzero(deg > 0)
    rng = np.random.default_rng(seed)
    return [int(v) for v in rng.choice(cand, size=count, replace=False)]


def make_graph(scale: int):
    from repro.graph import rmat1

    t0 = time.perf_counter()
    g = rmat1(scale, seed=0)
    return g, time.perf_counter() - t0


def check_against_dijkstra(g, source: int, states: dict) -> None:
    """Every state in ``states`` (name -> (n,) f32) equals the host
    Dijkstra from ``source``; integer weights keep f32 sums exact."""
    from repro.core import dijkstra_reference

    t0 = time.perf_counter()
    ref = dijkstra_reference(g, source).astype(np.float32)
    say(f"dijkstra source={source} host_s={time.perf_counter() - t0:.3f} "
        f"reached={int(np.isfinite(ref).sum())}/{g.n}")
    require(np.isfinite(ref).sum() > 1, f"source {source} reaches nothing")
    for name, state in states.items():
        require(np.array_equal(state, ref),
                f"{name} from source {source} differs from Dijkstra "
                f"at {int(np.sum(state != ref))} vertices")


def solve_phase(name, solver, g, sources) -> dict:
    from repro.api import Problem, SingleSource

    out = {}
    for v in sources:
        sol, c_s, r_s = timed(
            lambda: solver.solve(Problem(g, SingleSource(v)))
        )
        require(sol.metrics.converged, f"{name} source {v} did not converge")
        say(f"phase=solve spec={solver.config.name} source={v} "
            f"compile_s={c_s:.3f} run_s={r_s:.3f} "
            f"supersteps={sol.metrics.supersteps} {device_report()}")
        out[v] = sol.state
    return out


def one_chip(scale: int = SCALE_ONE_CHIP) -> None:
    from repro.api import Problem, SingleSource, Solver
    from repro.graph import graph_fingerprint
    from repro.launch.mesh import make_local_topology
    from repro.launch.serve import build_query_mix
    from repro.serve import (
        EdgeUpdate, LandmarkIndex, Router, SolutionCache, UpdateFeed,
    )

    mesh = make_local_topology(1).mesh
    g, gen_s = make_graph(scale)
    sources = pick_sources(g, 4, seed=1)
    a2a = Solver(SPEC_A2A, mesh=mesh)
    t0 = time.perf_counter()
    pg = a2a.partition(g)
    part_s = time.perf_counter() - t0
    say(f"phase=graph rmat1 scale={scale} n={g.n} m={g.m} "
        f"R={pg.rows_per_rank} W={pg.width} generate_s={gen_s:.3f} "
        f"partition_and_place_s={part_s:.3f}")

    # -- single solves, two exchanges --------------------------------
    ref = solve_phase("a2a", a2a, g, sources[:2])
    # free the a2a solver's device graph: the batched engine below needs
    # 13 of the chip's 16 GB (compile rehearsal)
    del a2a, pg
    sparse = Solver(SPEC_SPARSE, mesh=mesh)
    sparse.partition(g)
    sp = solve_phase("sparse", sparse, g, sources)
    for v in sources[:2]:
        check_against_dijkstra(g, v, {"a2a": ref[v], "sparse": sp[v]})
        require(same_bits(sp[v], ref[v]),
                f"sparse and a2a differ from source {v}")

    # -- batched solve: the landmark index over the four sources -----
    lm, c_s, r_s = timed(lambda: LandmarkIndex(
        sparse, g, landmarks=sources, symmetric=True
    ))
    say(f"phase=solve_batch spec={sparse.config.name} batch={lm.k} "
        f"compile_s={c_s:.3f} run_s={r_s:.3f} supersteps="
        f"{max(s.metrics.supersteps for s in lm.solutions)} "
        f"{device_report()}")
    for v, sol in zip(sources, lm.solutions):
        require(same_bits(sol.state, sp[v]),
                f"batch lane for source {v} differs from its single solve")

    # -- route service: mixed queries, then one edge update ----------
    # max_batch=1: on the chip a batched flush costs more per query
    # than single solves (see PERF.md)
    cache = SolutionCache()
    router = Router(sparse, g, cache=cache, landmarks=lm, max_batch=1)
    queries = build_query_mix(g, 16, 1.3, seed=0)

    def serve():
        tickets = []
        for q in queries:
            tickets.append(router.submit(q))
            router.pump()
        router.flush()
        return [t.result() for t in tickets]

    answers, c_s, r_s = timed(serve)
    by = {k: sum(a.served_by == k for a in answers)
          for k in ("batch", "cache", "landmark")}
    say(f"phase=router queries={len(answers)} served_by={by} "
        f"batches={router.stats.batches} compile_s={c_s:.3f} "
        f"run_s={r_s:.3f} {device_report()}")
    require(len(answers) == len(queries), "unanswered queries")
    for a in answers:
        q = a.query
        if a.estimated:
            require(a.lower <= a.upper, f"landmark bounds crossed: {a}")
        elif q.target is not None:
            require(a.distance == a.solution.state[q.target],
                    f"point-to-point answer disagrees with its solution "
                    f"for {q}")
        if a.solution is not None and q.source in sp:
            require(same_bits(a.solution.state, sp[q.source]),
                    f"served solution for source {q.source} differs "
                    "from its direct solve")

    # an edge that shortens a cached solution: warm refresh must move it
    fp_old = graph_fingerprint(g)
    key, before = max(cache.entries_for(fp_old),
                      key=lambda kv: int(np.isfinite(kv[1].state).sum()))
    src_state = before.state
    gain = src_state[g.src] + 0.25 * g.weight < src_state[g.dst]
    e = int(np.random.default_rng(2).choice(np.flatnonzero(gain)))
    upd = EdgeUpdate(int(g.src[e]), int(g.dst[e]), float(g.weight[e]) / 4)
    feed = UpdateFeed(g, sparse, cache=cache, landmarks=lm)
    res, c_s, r_s = timed(lambda: feed.apply(upd))
    say(f"phase=update edge=({upd.src},{upd.dst}) warm_refreshes="
        f"{res.warm_refreshes} cold_refreshes={res.cold_refreshes} "
        f"warm_supersteps={res.warm_supersteps} compile_s={c_s:.3f} "
        f"run_s={r_s:.3f}")
    require(res.improving and res.cold_refreshes == 0,
            f"improving update not refreshed warm: {res}")
    fresh = {k[1]: s for k, s in cache.entries_for(graph_fingerprint(g))}
    require(key[1] in fresh, "refreshed entry missing from the cache")
    cold, c_s, r_s = timed(
        lambda: sparse.solve(Problem(g, SingleSource(key[1])))
    )
    say(f"phase=cold_after_update source={key[1]} compile_s={c_s:.3f} "
        f"run_s={r_s:.3f} supersteps={cold.metrics.supersteps} "
        f"{device_report()}")
    require(same_bits(fresh[key[1]].state, cold.state),
            "warm-refreshed entry differs from a cold solve")
    require(cold.state[upd.dst] < src_state[upd.dst],
            "the update did not reach the solve (stale device graph?)")
    require(lm.fingerprint == graph_fingerprint(g), "landmarks not refreshed")


def compile_together(solvers, pg) -> None:
    """Compile each solver's single-query engine for ``pg`` in threads
    of its own: the host compiles separate programs in parallel, and
    the solves that follow reuse them."""
    from repro.api import get_processing
    from repro.core.engine import initial_state

    state = initial_state(pg, get_processing("sssp"), [])
    jobs = [(s.compiled(pg.n_parts, pg.n_local), pg.on_mesh(s.mesh))
            for s in solvers]
    with ThreadPoolExecutor(len(jobs)) as pool:
        futures = [pool.submit(lambda fn=fn, graph=graph:
                               fn.lower(*graph, *state).compile())
                   for fn, graph in jobs]
        for f in futures:
            f.result()


def four_chips(scale: int = SCALE_FOUR_CHIPS) -> None:
    from repro.api import Problem, SingleSource, Solver
    from repro.launch.mesh import make_local_topology

    require(jax.device_count() >= 4,
            f"--chips 4 needs four devices, found {jax.device_count()}")
    mesh = make_local_topology(4).mesh
    g, gen_s = make_graph(scale)
    solvers = {s: Solver(s, mesh=mesh) for s in (SPEC_A2A, SPEC_SPARSE,
                                                 SPEC_PMIN)}
    t0 = time.perf_counter()
    pg = solvers[SPEC_A2A].partition(g)
    part_s = time.perf_counter() - t0
    say(f"phase=graph rmat1 scale={scale} n={g.n} m={g.m} P={pg.n_parts} "
        f"R={pg.rows_per_rank} W={pg.width} generate_s={gen_s:.3f} "
        f"partition_and_place_s={part_s:.3f}")

    devices = set(jax.devices()[:4])
    for s in solvers.values():
        require(s.n_devices == 4 and set(s.mesh.devices.flat) == devices,
                f"solver mesh spans {s.mesh.devices.shape}, not 4 chips")
    for name, arr in zip(("row_src", "col", "wgt"), pg.on_mesh(mesh)):
        shards = arr.addressable_shards
        require({sh.device for sh in shards} == devices
                and all(sh.data.shape[0] == 1 for sh in shards),
                f"{name} is not split one rank per chip")
        say(f"{name} {arr.shape} sharded over "
            f"{sorted(sh.device.id for sh in shards)} "
            f"per-chip {shards[0].data.shape}")

    _, c_s, _ = timed(lambda: compile_together(solvers.values(), pg))
    say(f"phase=compile engines={len(solvers)} compile_s={c_s:.3f}")
    (source,) = pick_sources(g, 1, seed=1)
    states = {}
    for spec, solver in solvers.items():
        states[spec] = solve_phase(spec, solver, pg, [source])[source]
    check_against_dijkstra(g, source, states)
    base = states[SPEC_A2A]
    for spec, state in states.items():
        require(same_bits(state, base), f"{spec} differs from {SPEC_A2A}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    d = devices[0]
    say(f"jax {jax.__version__} devices={devices}")
    for dev in devices:
        say(f"device {dev.id}: platform={dev.platform} "
            f"kind={dev.device_kind}")
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {d.platform}",
              file=sys.stderr)
        return 2

    from repro.launch.mesh import use_compile_cache

    say(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    say(f"all checks passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
