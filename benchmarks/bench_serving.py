"""Serving SLO benchmark: the persistent query service under a
Zipf-skewed mix on the 8-device mesh → ``BENCH_serving.json``.

Two scenarios, run in a subprocess with 8 placeholder host devices:

* **query mix** — N queries (70% single-source, 20% point-to-point
  exact, 10% landmark-estimated), sources Zipf-skewed so the solution
  cache has a hot set.  Reports queries/sec, p50/p90/p99 latency,
  cache hit rate, admission-batch count and landmark serve count.
  Engine-compile time is excluded by pre-warming the power-of-two
  batch buckets (a deployed service pre-warms at rollout).
* **streamed updates** — improving edge updates (weight drops + an
  insertion) applied through the UpdateFeed while answers stay cached:
  every warm-restart-refreshed entry must be *bit-identical* to a
  from-scratch cold solve of the updated graph while spending strictly
  fewer engine supersteps (the self-stabilization dividend the paper
  promises).  A non-improving update is also applied to exercise the
  stale-detection → cold-solve path.

CLI:  PYTHONPATH=src python benchmarks/bench_serving.py \
          [--quick] [--scale N] [--json BENCH_serving.json]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

CHILD = r"""
import json, time
import numpy as np, jax
assert len(jax.devices()) == 8, jax.devices()
from repro.api import Problem, SingleSource, Solver
from repro.core import dijkstra_reference
from repro.graph import rmat1, graph_fingerprint
from repro.serve import (EdgeUpdate, LandmarkIndex, Query, Router,
                         SolutionCache, UpdateFeed, serve_latency_stats)

SCALE = %(scale)d
QUICK = %(quick)d
N_QUERIES = 120 if QUICK else 400
N_UPDATES = 3 if QUICK else 6
K = 4 if QUICK else 8
MAX_BATCH = 8
ZIPF_A = 1.3

g = rmat1(SCALE, seed=7)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
solver = Solver("%(spec)s", mesh=mesh)
cache = SolutionCache(byte_budget=256 << 20)
t0 = time.perf_counter()
lm = LandmarkIndex(solver, g, k=K, symmetric=True)
landmark_build_s = time.perf_counter() - t0
router = Router(solver, g, cache=cache, landmarks=lm,
                max_batch=MAX_BATCH, max_wait_s=0.01)

rng = np.random.default_rng(0)
ranks = np.minimum(rng.zipf(ZIPF_A, size=N_QUERIES) - 1, g.n - 1)
perm = np.random.default_rng(1).permutation(g.n)
srcs = perm[ranks]
tgts = rng.integers(0, g.n, size=N_QUERIES)
kinds = rng.random(N_QUERIES)
queries = []
for s, t, k in zip(srcs, tgts, kinds):
    if k < 0.7:
        queries.append(Query(int(s)))
    elif k < 0.9:
        queries.append(Query(int(s), target=int(t)))
    else:
        queries.append(Query(int(s), target=int(t), exact=False))

# pre-warm the batch buckets (compile time out of the SLO window)
router.serve(queries[:MAX_BATCH])
router.serve([queries[0]])
cache.clear()
cache.stats.hits = cache.stats.misses = 0

t0 = time.perf_counter()
tickets = [router.submit(q) for q in queries]
router.flush()
wall_s = time.perf_counter() - t0
answers = [t.result() for t in tickets]
lat = serve_latency_stats(answers)

# correctness spot check: exact answers vs the Dijkstra oracle,
# estimates sandwiched by their bounds
checked = 0
for a in answers[:50]:
    ref = dijkstra_reference(g, a.query.source)
    if a.served_by == "landmark":
        d = ref[a.query.target]
        assert a.lower <= d <= a.upper or (
            np.isinf(d) and np.isinf(a.upper)), (a, d)
    elif a.query.target is not None:
        r = ref[a.query.target]
        assert a.distance == r or (np.isinf(a.distance) and np.isinf(r))
    else:
        assert np.allclose(np.where(np.isinf(ref), -1, ref),
                           np.where(np.isinf(a.solution.state), -1,
                                    a.solution.state))
    checked += 1

serving = dict(
    ok=True, n_queries=len(answers), wall_s=wall_s,
    qps=len(answers) / wall_s,
    p50_ms=lat.p50_s * 1e3, p90_ms=lat.p90_s * 1e3,
    p99_ms=lat.p99_s * 1e3,
    hit_rate=cache.stats.hit_rate(),
    cache=cache.stats.as_dict(), router=router.stats.as_dict(),
    landmark_build_s=landmark_build_s, spot_checked=checked,
)

# ---- streamed-update scenario ------------------------------------
# small resident set so each update's eager refresh cost is visible
cache.clear()
hot = sorted({int(v) for v in srcs[:10]})[:6]
router.serve([Query(v) for v in hot])
feed = UpdateFeed(g, solver, cache=cache, landmarks=lm)
update_rows = []
for i in range(N_UPDATES):
    if i == 1:
        # an insertion: a brand-new cheap edge (improving by definition)
        u, v = int(perm[0]), int(perm[1])
        while v == u or ((g.src == u) & (g.dst == v)).any():
            v = int(rng.integers(0, g.n))
        upd = EdgeUpdate(u, v, 1.0)
    else:
        e = int(rng.integers(0, g.m))
        upd = EdgeUpdate(int(g.src[e]), int(g.dst[e]),
                         float(g.weight[e]) * 0.25)
    res = feed.apply(upd)
    fp = graph_fingerprint(g)
    cold_supersteps = 0
    identical = True
    for key, sol in cache.entries_for(fp):
        cold = solver.solve(Problem(g, SingleSource(key[1])))
        identical &= bool(np.array_equal(sol.state, cold.state))
        cold_supersteps += cold.metrics.supersteps
    update_rows.append(dict(
        improving=res.improving, inserted=res.inserted,
        warm_refreshes=res.warm_refreshes,
        warm_supersteps=res.warm_supersteps,
        cold_supersteps=cold_supersteps,
        bit_identical=identical,
        ok=bool(identical and res.improving
                and res.warm_supersteps < cold_supersteps),
    ))

# non-improving update: stale answers must be detected and re-solved
e = int(rng.integers(0, g.m))
res = feed.apply(EdgeUpdate(int(g.src[e]), int(g.dst[e]), 1e6))
fp = graph_fingerprint(g)
identical = True
for key, sol in cache.entries_for(fp):
    cold = solver.solve(Problem(g, SingleSource(key[1])))
    identical &= bool(np.array_equal(sol.state, cold.state))
nonimp = dict(
    improving=res.improving, invalidated=res.invalidated,
    cold_refreshes=res.cold_refreshes, bit_identical=identical,
    ok=bool(identical and not res.improving and res.cold_refreshes > 0),
)

out = dict(
    scale=SCALE, spec="%(spec)s", n_devices=8,
    serving=serving, updates=update_rows, non_improving=nonimp,
    ok=bool(serving["ok"] and all(r["ok"] for r in update_rows)
            and nonimp["ok"]),
)
print(json.dumps(out))
"""


def _run_child(child: str, timeout: int = 3000) -> dict:
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
    scale: int = 10, quick: bool = False,
    spec: str = "delta:5+threadq/a2a",
) -> dict:
    return _run_child(CHILD % {
        "scale": scale, "quick": int(quick), "spec": spec,
    })


def main(
    scale: int = 10, quick: bool = False, json_path: str | None = None,
    spec: str = "delta:5+threadq/a2a",
) -> list[str]:
    out = run(scale, quick=quick, spec=spec)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
    assert out["ok"], out
    s = out["serving"]
    lines = [
        f"serving/rmat1_s{out['scale']}/{out['spec']}"
        f",{s['qps']:.1f}qps"
        f",p50={s['p50_ms']:.1f}ms,p90={s['p90_ms']:.1f}ms"
        f",p99={s['p99_ms']:.1f}ms,hit_rate={s['hit_rate']:.3f}"
        f",landmark={s['router']['landmark_served']}"
    ]
    for i, u in enumerate(out["updates"]):
        lines.append(
            f"serving/update{i}/"
            f"{'insert' if u['inserted'] else 'drop'}"
            f",warm_steps={u['warm_supersteps']}"
            f",cold_steps={u['cold_supersteps']}"
            f",identical={u['bit_identical']}"
        )
    n = out["non_improving"]
    lines.append(
        f"serving/non_improving,invalidated={n['invalidated']}"
        f",cold={n['cold_refreshes']},identical={n['bit_identical']}"
    )
    return lines


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small mix + scale 9 (CI trajectory job)")
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--spec", default="delta:5+threadq/a2a")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump the raw scenario dict as JSON")
    a = ap.parse_args()
    scale = a.scale if a.scale is not None else (9 if a.quick else 10)
    for line in main(scale, quick=a.quick, json_path=a.json, spec=a.spec):
        print(line)
