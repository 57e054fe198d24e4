"""Serving driver: run the persistent SSSP query service against a
Zipf-skewed synthetic query mix, with optional streamed edge updates.

    PYTHONPATH=src python -m repro.launch.serve --graph rmat1 --scale 10 \
        --queries 200 --landmarks 8 --updates 4
    # 8-device smoke (CI):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --scale 9 --queries 100

Builds the full serving stack (Router + SolutionCache + LandmarkIndex
+ UpdateFeed) on one long-lived Solver, serves the mix through the
admission batcher, then applies improving updates and verifies that
warm-restart-refreshed answers are bit-identical to cold solves.
Prints queries/sec, p50/p99 latency, and cache hit rate.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def zipf_sources(n: int, count: int, a: float, rng) -> np.ndarray:
    """Zipf-skewed vertex ids: rank r drawn with p ∝ r^-a, mapped onto
    a fixed random permutation of the vertex ids so the hot set is not
    an artifact of id order."""
    ranks = rng.zipf(a, size=count)
    ranks = np.minimum(ranks - 1, n - 1)
    perm = np.random.default_rng(0).permutation(n)
    return perm[ranks]


def build_query_mix(g, count: int, zipf_a: float, seed: int):
    """70% single-source, 20% point-to-point exact, 10% estimated."""
    from repro.serve import Query

    rng = np.random.default_rng(seed)
    srcs = zipf_sources(g.n, count, zipf_a, rng)
    tgts = rng.integers(0, g.n, size=count)
    kinds = rng.random(count)
    out = []
    for s, t, k in zip(srcs, tgts, kinds):
        if k < 0.7:
            out.append(Query(int(s)))
        elif k < 0.9:
            out.append(Query(int(s), target=int(t)))
        else:
            out.append(Query(int(s), target=int(t), exact=False))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--graph", default="rmat1",
                    choices=["rmat1", "rmat2", "road", "smallworld"])
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--spec", default="delta:5+threadq/a2a")
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--zipf", type=float, default=1.3,
                    help="Zipf exponent of the source skew")
    ap.add_argument("--landmarks", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=10.0)
    ap.add_argument("--cache-mb", type=int, default=256)
    ap.add_argument("--updates", type=int, default=4,
                    help="streamed improving edge updates to apply "
                         "after the query mix (0 disables)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text exposition on "
                         "http://127.0.0.1:PORT/metrics (and a JSON "
                         "/stats) from a daemon thread; 0 picks a "
                         "free port")
    ap.add_argument("--stats-text", action="store_true",
                    help="print the Prometheus text exposition after "
                         "the run (works without --metrics-port)")
    args = ap.parse_args()

    from repro.api import Problem, SingleSource, Solver
    from repro.launch.mesh import make_local_topology, use_compile_cache
    from repro.launch.sssp import build_graph
    from repro.serve import (
        EdgeUpdate, LandmarkIndex, Router, SolutionCache, UpdateFeed,
        serve_latency_stats,
    )

    use_compile_cache()
    g = build_graph(args.graph, args.scale, args.seed)
    topo = make_local_topology()
    solver = Solver(args.spec, mesh=topo.mesh)
    print(f"[serve] {g.name}: n={g.n} m={g.m} spec={solver.config.name} "
          f"devices={solver.n_devices}")

    # live observability: tracer feeds the registry (span histograms +
    # event counters); --metrics-port exposes it over HTTP
    registry = server = None
    if args.metrics_port is not None or args.stats_text:
        from repro.obs import MetricsRegistry, Tracer, serve_metrics, set_tracer

        registry = MetricsRegistry()
        set_tracer(Tracer(registry=registry))
        if args.metrics_port is not None:
            server = serve_metrics(registry, args.metrics_port)
            print(f"[serve] metrics: http://{server.server_address[0]}:"
                  f"{server.server_address[1]}/metrics (+ /stats)")

    cache = SolutionCache(byte_budget=args.cache_mb << 20)
    t0 = time.perf_counter()
    lm = LandmarkIndex(solver, g, k=args.landmarks, symmetric=True)
    print(f"[serve] landmark tier: K={lm.k} built in "
          f"{time.perf_counter() - t0:.2f}s ({lm.nbytes} bytes)")
    router = Router(
        solver, g, cache=cache, landmarks=lm,
        max_batch=args.max_batch, max_wait_s=args.max_wait_ms / 1e3,
    )
    if registry is not None:
        # callback gauges: the exposition always reads live state
        registry.gauge("repro_router_queries_total",
                       help="queries admitted", fn=lambda: router.stats.queries)
        registry.gauge("repro_router_batches_total",
                       help="admission flushes", fn=lambda: router.stats.batches)
        registry.gauge("repro_router_latency_p99_seconds",
                       help="p99 over the latency ring",
                       fn=lambda: router.latency_stats().p99_s)
        registry.gauge("repro_router_latency_p50_seconds",
                       help="p50 over the latency ring",
                       fn=lambda: router.latency_stats().p50_s)
        registry.gauge("repro_cache_hits_total",
                       help="solution-cache hits", fn=lambda: cache.stats.hits)
        registry.gauge("repro_cache_misses_total",
                       help="solution-cache misses",
                       fn=lambda: cache.stats.misses)
        registry.gauge("repro_engine_traces_total",
                       help="process-wide jit traces",
                       fn=lambda: solver.stats()["engine_cache"]["traces"])

    queries = build_query_mix(g, args.queries, args.zipf, args.seed)
    # warm the compile caches outside the timed window (a real service
    # pre-warms its buckets at deploy time)
    router.serve(queries[: args.max_batch])
    cache.clear()
    cache.stats.hits = cache.stats.misses = 0

    t0 = time.perf_counter()
    tickets = []
    for q in queries:
        tickets.append(router.submit(q))
        router.pump()
    router.flush()
    wall = time.perf_counter() - t0
    answers = [t.result() for t in tickets]

    lat = serve_latency_stats(answers)
    print(f"[serve] {len(answers)} queries in {wall:.2f}s = "
          f"{len(answers) / wall:.1f} q/s")
    print(f"[serve] latency {lat}")
    print(f"[serve] cache {cache.stats}")
    print(f"[serve] router {router.stats.as_dict()}")
    print(f"[serve] solver {solver.stats()}")

    if args.updates:
        feed = UpdateFeed(g, solver, cache=cache, landmarks=lm)
        rng = np.random.default_rng(args.seed + 1)
        warm_total = cold_total = 0
        for _ in range(args.updates):
            e = int(rng.integers(0, g.m))
            res = feed.apply(EdgeUpdate(
                int(g.src[e]), int(g.dst[e]),
                float(g.weight[e]) * 0.25,
            ))
            warm_total += res.warm_supersteps
            cold_total += res.cold_supersteps
        print(f"[serve] applied {args.updates} improving updates: "
              f"{feed.stats.as_dict()}")
        # freshness check: every refreshed entry must equal a cold solve
        from repro.graph import graph_fingerprint

        checked = 0
        for key, sol in cache.entries_for(graph_fingerprint(g))[:3]:
            cold = solver.solve(Problem(g, SingleSource(key[1])))
            assert np.array_equal(sol.state, cold.state), key
            checked += 1
        print(f"[serve] {checked} refreshed entries verified "
              f"bit-identical to cold solves "
              f"(warm supersteps={warm_total})")

    if args.stats_text and registry is not None:
        print("[serve] Prometheus exposition:")
        print(registry.expose())
    if server is not None:
        server.shutdown()


if __name__ == "__main__":
    main()
