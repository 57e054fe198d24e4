"""repro.serve: admission batching, solution cache, landmark tier,
streaming updates, and the incremental fingerprint chain.

Single-device fast tests here; the 8-device serving smoke runs in a
subprocess (marked slow) like the other multi-device coverage.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

import repro.api as api
from repro.api import Problem, SingleSource, Solver, batch_bucket
from repro.core import LatencyStats, dijkstra_reference
from repro.graph import (
    chain_fingerprint, clear_fingerprint_chain, graph_fingerprint, rmat1,
)
from repro.serve import (
    EdgeUpdate, LandmarkIndex, Query, Router, SolutionCache, UpdateFeed,
)


def close(a, b):
    return np.allclose(
        np.where(np.isinf(a), -1, a), np.where(np.isinf(b), -1, b)
    )


@pytest.fixture(scope="module")
def mesh1():
    return jax.make_mesh((1,), ("data",))


@pytest.fixture(scope="module")
def solver(mesh1):
    return Solver("delta:5+threadq/a2a", mesh=mesh1)


def fresh_graph(seed=3):
    """A private graph per test — update tests mutate edges in place,
    which must not leak into session-scoped fixtures."""
    return rmat1(8, seed=seed)


# ------------------------------------------------- fingerprint chain


def test_chain_fingerprint_is_incremental_and_ordered():
    g1, g2 = fresh_graph(), fresh_graph()
    base = graph_fingerprint(g1)
    assert base == graph_fingerprint(g2)
    a = EdgeUpdate(0, 1, 2.0).record()
    b = EdgeUpdate(1, 0, 3.0).record()
    # same update sequence -> same token; different order -> different
    fa1 = chain_fingerprint(g1, a)
    fa2 = chain_fingerprint(g2, a)
    assert fa1 == fa2 and fa1 != base
    fb1 = chain_fingerprint(g1, b)
    g3 = fresh_graph()
    chain_fingerprint(g3, b)
    fb3 = chain_fingerprint(g3, a)
    assert fb1 != fb3  # order-sensitive hash chain
    # the chained token is what lookups now return, O(1)
    assert graph_fingerprint(g1) == fb1
    # full=True bypasses the chain (the O(m) oracle)
    assert graph_fingerprint(g1, full=True) == base
    clear_fingerprint_chain(g1)
    assert graph_fingerprint(g1) == base


def test_chain_fingerprint_tracks_full_rehash_oracle():
    """The chain must distinguish graphs exactly when the full-rehash
    oracle does: after applying an actual mutation + its record, both
    the chain token and the full rehash change."""
    g = fresh_graph()
    full_before = graph_fingerprint(g, full=True)
    upd = EdgeUpdate(int(g.src[5]), int(g.dst[5]),
                     float(g.weight[5]) * 0.5)
    g.weight[5] *= 0.5
    token = chain_fingerprint(g, upd.record())
    assert graph_fingerprint(g, full=True) != full_before  # oracle moved
    assert token != full_before                            # chain moved too
    # chained tokens live in a distinct space from full-rehash tokens
    assert token != graph_fingerprint(g, full=True)


# ------------------------------------------------------------- cache


def _solution_for(solver, g, v):
    return solver.solve(Problem(g, SingleSource(v)))


def test_cache_lru_hit_miss_counters(solver, tiny_graphs):
    g = tiny_graphs[0]
    fp = graph_fingerprint(g)
    cache = SolutionCache(byte_budget=1 << 20)
    key = SolutionCache.key_for(fp, 0, solver.config.name)
    assert cache.get(key) is None
    cache.put(key, _solution_for(solver, g, 0))
    assert cache.get(key) is not None
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert cache.stats.bytes > 0
    assert cache.stats.hit_rate() == 0.5
    # peek doesn't skew counters
    assert cache.peek(key) is not None
    assert cache.stats.hits == 1


def test_cache_byte_budget_evicts_lru(solver, tiny_graphs):
    g = tiny_graphs[0]
    fp = graph_fingerprint(g)
    one = _solution_for(solver, g, 0)
    cache = SolutionCache(byte_budget=int(one.nbytes * 2.5))
    keys = [SolutionCache.key_for(fp, v, solver.config.name)
            for v in range(4)]
    for k, v in zip(keys, range(4)):
        cache.put(k, _solution_for(solver, g, v))
    assert len(cache) == 2  # budget fits two solutions
    assert cache.stats.evictions == 2
    assert cache.peek(keys[0]) is None      # oldest evicted
    assert cache.peek(keys[3]) is not None  # newest resident
    assert cache.stats.bytes <= cache.byte_budget
    # an over-budget single entry stays resident alone
    tiny = SolutionCache(byte_budget=1)
    tiny.put(keys[0], one)
    assert len(tiny) == 1


def test_cache_invalidate_graph(solver, tiny_graphs):
    g = tiny_graphs[0]
    fp = graph_fingerprint(g)
    cache = SolutionCache()
    for v in range(3):
        cache.put(SolutionCache.key_for(fp, v, solver.config.name),
                  _solution_for(solver, g, v))
    other = ("other",)
    cache.put(SolutionCache.key_for(other, 0, solver.config.name),
              _solution_for(solver, g, 0))
    assert cache.invalidate_graph(fp) == 3
    assert len(cache) == 1 and cache.stats.invalidations == 3
    assert cache.entries_for(fp) == []


# ------------------------------------------- batch bucketing (solver)


def test_batch_bucket_rounding():
    assert [batch_bucket(b) for b in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]
    with pytest.raises(ValueError):
        batch_bucket(0)


def test_solve_batch_bucketing_no_retrace(tiny_graphs, mesh1):
    """Varying batch sizes within one power-of-two bucket must reuse
    the compiled engine — the serving-loop retrace regression."""
    g = tiny_graphs[0]
    solver = Solver("delta:7+threadq/a2a", mesh=mesh1)
    mk = lambda vs: [Problem(g, SingleSource(v)) for v in vs]
    solver.solve_batch(mk([0, 1, 2]))  # warms the bucket-4 engine
    before = api.trace_count()
    solver.solve_batch(mk([3, 4, 5, 6]))      # B=4 -> same bucket
    solver.solve_batch(mk([7, 8]))            # B=2 -> bucket 2: traces
    traced_b2 = api.trace_count() - before
    solver.solve_batch(mk([9, 10]))           # B=2 again: cached
    assert api.trace_count() - before == traced_b2
    assert traced_b2 <= 1
    # padded lanes don't corrupt results
    sols = solver.solve_batch(mk([0, 5, 11]))
    for v, sol in zip([0, 5, 11], sols):
        assert close(dijkstra_reference(g, v), sol.state)
    assert len(sols) == 3


def test_solution_seams(solver, tiny_graphs):
    g = tiny_graphs[0]
    sol = solver.solve(Problem(g, SingleSource(3)))
    assert sol.source == 3
    assert sol.nbytes == sol.state.nbytes + sol.padded.nbytes
    assert sol.distance_to(3) == 0.0
    ref = dijkstra_reference(g, 3)
    assert sol.distance_to(7) == ref[7] or (
        np.isinf(sol.distance_to(7)) and np.isinf(ref[7]))
    with pytest.raises(ValueError):
        sol.distance_to(g.n)
    assert api.engine_cache_info()["size"] > 0
    info = solver.stats()
    assert info["partition_memo_size"] >= 1


# ------------------------------------------------------------ router


def test_router_serves_correct_answers(solver, tiny_graphs):
    g = tiny_graphs[0]
    router = Router(solver, g, max_batch=4)
    ans = router.serve([
        Query(0), Query(5, target=9), Query(0, target=2),
    ])
    ref0, ref5 = dijkstra_reference(g, 0), dijkstra_reference(g, 5)
    assert close(ref0, ans[0].solution.state)
    assert ans[1].distance == ref5[9]
    assert ans[2].distance == ref0[2]
    assert ans[2].served_by in ("cache", "batch")
    assert all(a.latency_s >= 0 for a in ans)


def test_router_cache_hits_and_dedupe(solver, tiny_graphs):
    g = tiny_graphs[0]
    router = Router(solver, g, max_batch=8)
    router.serve([Query(0), Query(0, target=1), Query(0, target=2)])
    # one distinct source -> one solve, and repeats hit the cache
    assert router.stats.batched_solves == 1
    ans = router.serve([Query(0)])
    assert ans[0].served_by == "cache"
    assert router.cache.stats.hits >= 1


def test_router_size_trigger_flushes(solver, tiny_graphs):
    g = tiny_graphs[0]
    router = Router(solver, g, max_batch=2)
    t1 = router.submit(Query(0))
    assert not t1.done
    t2 = router.submit(Query(5))  # fills the batch -> auto flush
    assert t1.done and t2.done


def test_router_timeout_trigger(solver, tiny_graphs):
    """Pad/timeout batching with an injected clock: pump() flushes
    once the oldest pending query exceeds max_wait_s."""
    g = tiny_graphs[0]
    now = [0.0]
    router = Router(solver, g, max_batch=64, max_wait_s=0.5,
                    clock=lambda: now[0])
    t = router.submit(Query(0))
    assert not router.pump() and not t.done
    now[0] = 0.6
    assert router.pump() and t.done
    assert t.answer.latency_s == pytest.approx(0.6)


def test_router_ticket_result_forces_flush(solver, tiny_graphs):
    g = tiny_graphs[0]
    router = Router(solver, g, max_batch=64)
    t = router.submit(Query(7))
    ans = t.result()  # blocking caller is the ultimate latency trigger
    assert close(dijkstra_reference(g, 7), ans.solution.state)


# --------------------------------------------------------- landmarks


def test_landmark_bounds_sandwich_truth(solver, tiny_graphs):
    g = tiny_graphs[0]  # rmat1 is symmetrized by construction
    lm = LandmarkIndex(solver, g, k=4, symmetric=True)
    assert lm.k == 4 and lm.dist.shape == (4, g.n)
    rng = np.random.default_rng(0)
    refs = {}
    for s in rng.integers(0, g.n, 5):
        s = int(s)
        if s not in refs:
            refs[s] = dijkstra_reference(g, s)
        for t in rng.integers(0, g.n, 4):
            est = lm.estimate(s, int(t))
            d = refs[s][int(t)]
            if np.isinf(d):
                assert np.isinf(est.upper)
            else:
                assert est.lower <= d <= est.upper, (s, int(t), d, est)
    est = lm.estimate(3, 3)
    assert est.exact and est.upper == 0.0
    # a landmark as endpoint pinches the bounds to exact
    hub = lm.landmarks[0]
    tgt = int(np.flatnonzero(np.isfinite(lm.dist[0]))[1])
    est = lm.estimate(hub, tgt)
    assert est.exact and est.upper == lm.dist[0, tgt]


def test_router_landmark_tier_and_escalation(solver, tiny_graphs):
    g = tiny_graphs[0]
    lm = LandmarkIndex(solver, g, k=4, symmetric=True)
    router = Router(solver, g, landmarks=lm, max_batch=4)
    a = router.serve([Query(0, target=9, exact=False)])[0]
    assert a.served_by == "landmark" and a.lower <= a.upper
    assert a.distance == a.upper
    assert router.stats.landmark_served == 1
    # exact= escalation goes through the engine and nails the truth
    b = router.serve([Query(0, target=9, exact=True)])[0]
    assert b.served_by in ("cache", "batch")
    ref = dijkstra_reference(g, 0)[9]
    assert b.distance == ref
    assert a.lower <= b.distance <= a.upper
    # without an index, estimate queries silently escalate
    router2 = Router(solver, g, max_batch=4)
    c = router2.serve([Query(0, target=9, exact=False)])[0]
    assert c.served_by in ("cache", "batch") and c.distance == ref


# ---------------------------------------------------- streaming updates


def test_feed_improving_drop_warm_refresh_bit_identical(solver):
    g = fresh_graph()
    router = Router(solver, g, max_batch=4)
    router.serve([Query(0), Query(5)])
    feed = UpdateFeed(g, solver, cache=router.cache)
    e = 17
    res = feed.apply(EdgeUpdate(int(g.src[e]), int(g.dst[e]),
                                float(g.weight[e]) * 0.25))
    assert res.improving and not res.inserted
    assert res.warm_refreshes == 2 and res.cold_refreshes == 0
    fp = graph_fingerprint(g)
    assert res.fingerprint == fp
    entries = router.cache.entries_for(fp)
    assert len(entries) == 2
    cold_steps = 0
    for key, sol in entries:
        cold = solver.solve(Problem(g, SingleSource(key[1])))
        assert np.array_equal(sol.state, cold.state)  # bit-identical
        assert close(dijkstra_reference(g, key[1]), sol.state)
        cold_steps += cold.metrics.supersteps
    assert res.warm_supersteps < cold_steps  # strictly fewer supersteps


def test_feed_update_refreshes_device_graph(solver):
    """The partitioned graph is placed on the solver's mesh once, with
    the engine's sharding; an update places the changed graph anew, so
    a warm restart reads the new weight and not a stale device copy."""
    g = fresh_graph()
    pg0 = solver.partition(g)
    placed = pg0.on_mesh(solver.mesh)
    want = NamedSharding(solver.mesh, PartitionSpec(solver.mesh.axis_names))
    for dev, host in zip(placed, (pg0.row_src, pg0.col, pg0.wgt)):
        assert isinstance(dev, jax.Array)
        assert dev.sharding.is_equivalent_to(want, dev.ndim)
        assert np.array_equal(np.asarray(dev), host)
    assert solver.partition(g).on_mesh(solver.mesh) is placed  # once

    e = 17
    u, v = int(g.src[e]), int(g.dst[e])
    new_w = np.float32(float(g.weight[e]) * 0.25)
    UpdateFeed(g, solver).apply(EdgeUpdate(u, v, float(new_w)))
    pg1 = solver.partition(g)
    wgt1 = np.asarray(pg1.on_mesh(solver.mesh)[2])
    assert np.array_equal(wgt1, pg1.wgt)
    rank, slot = (int(x) for x in pg1.owner_slot(u))
    edge = ((pg1.row_src[rank] == slot)[:, None]
            & (pg1.col[rank] == int(pg1.padded_id(v))))
    assert wgt1[rank][edge].min() == new_w
    assert np.asarray(placed[2])[rank][edge].min() > new_w


def test_feed_insertion_is_improving(solver):
    g = fresh_graph()
    m_before = g.m
    router = Router(solver, g, max_batch=4)
    router.serve([Query(0)])
    feed = UpdateFeed(g, solver, cache=router.cache)
    # a new cheap edge from the source shortens real paths
    src = 0
    dst = (src + 1) % g.n
    while ((g.src == src) & (g.dst == dst)).any():
        dst = (dst + 1) % g.n
    res = feed.apply(EdgeUpdate(src, dst, 0.5))
    assert res.improving and res.inserted
    assert g.m == m_before + 1
    [(key, sol)] = router.cache.entries_for(graph_fingerprint(g))
    cold = solver.solve(Problem(g, SingleSource(0)))
    assert np.array_equal(sol.state, cold.state)
    assert sol.state[dst] <= 0.5  # the new edge is live


def test_feed_non_improving_detected_and_cold_solved(solver):
    """Weight increases and deletions: served results must be detected
    stale and routed to a cold solve, bit-identical to from-scratch."""
    g = fresh_graph()
    router = Router(solver, g, max_batch=4)
    router.serve([Query(0), Query(5)])
    fp_old = graph_fingerprint(g)
    feed = UpdateFeed(g, solver, cache=router.cache)
    e = 3
    res = feed.apply(EdgeUpdate(int(g.src[e]), int(g.dst[e]),
                                float(g.weight[e]) * 100.0))
    assert not res.improving
    assert res.invalidated == 2 and res.cold_refreshes == 2
    # old-fingerprint entries are unreachable, new ones are fresh
    assert router.cache.entries_for(fp_old) == []
    for key, sol in router.cache.entries_for(graph_fingerprint(g)):
        fresh = solver.solve(Problem(g, SingleSource(key[1])))
        assert np.array_equal(sol.state, fresh.state)
        assert close(dijkstra_reference(g, key[1]), sol.state)
    # deletion is non-improving too (weight -> +inf)
    e2 = 9
    res2 = feed.apply(EdgeUpdate(int(g.src[e2]), int(g.dst[e2]),
                                 delete=True))
    assert not res2.improving and res2.cold_refreshes == 2
    assert np.isinf(g.weight[e2])
    for key, sol in router.cache.entries_for(graph_fingerprint(g)):
        assert close(dijkstra_reference(g, key[1]), sol.state)


def test_feed_lazy_mode_invalidates_only(solver):
    g = fresh_graph()
    router = Router(solver, g, max_batch=4)
    router.serve([Query(0)])
    feed = UpdateFeed(g, solver, cache=router.cache, refresh="lazy")
    e = 11
    res = feed.apply(EdgeUpdate(int(g.src[e]), int(g.dst[e]),
                                float(g.weight[e]) * 0.25))
    # lazy: nothing refreshed, entry dropped; next query cold-misses
    assert res.warm_refreshes == 0 and res.invalidated == 1
    assert len(router.cache) == 0
    a = router.serve([Query(0)])[0]
    assert a.served_by == "batch"
    assert close(dijkstra_reference(g, 0), a.solution.state)


def test_feed_layout_change_falls_back_to_cold(mesh1):
    """Under a data-dependent partitioner (ebal) an update can move the
    ownership boundaries; resolve refuses and the feed cold-solves."""
    g = fresh_graph()
    solver = Solver("delta:5+threadq/a2a@ebal", mesh=mesh1)
    router = Router(solver, g, max_batch=4)
    router.serve([Query(0)])
    feed = UpdateFeed(g, solver, cache=router.cache)
    # insertions change degree counts, which is what moves ebal rows
    rng = np.random.default_rng(0)
    res = None
    for _ in range(6):
        u = int(rng.integers(0, g.n))
        v = int(rng.integers(0, g.n))
        if u == v or ((g.src == u) & (g.dst == v)).any():
            continue
        res = feed.apply(EdgeUpdate(u, v, 1.0))
    assert res is not None and res.improving
    # whichever path it took, the cached answer matches the oracle
    [(key, sol)] = router.cache.entries_for(graph_fingerprint(g))
    assert close(dijkstra_reference(g, key[1]), sol.state)


def test_feed_validates_inputs(solver):
    g = fresh_graph()
    feed = UpdateFeed(g, solver)
    with pytest.raises(ValueError):
        feed.apply(EdgeUpdate(g.n, 0, 1.0))
    with pytest.raises(ValueError):
        feed.apply(EdgeUpdate(0, 1, -2.0))
    with pytest.raises(ValueError):
        UpdateFeed(g, solver, refresh="sometimes")


# ----------------------------------------------------- latency stats


def test_latency_stats_nearest_rank():
    xs = [float(i) for i in range(1, 101)]  # 1..100
    st = LatencyStats.from_samples(xs)
    assert st.count == 100 and st.p50_s == 50.0
    assert st.p90_s == 90.0 and st.p99_s == 99.0 and st.max_s == 100.0
    assert LatencyStats.from_samples([]).count == 0
    one = LatencyStats.from_samples([0.25])
    assert one.p50_s == one.p99_s == one.max_s == 0.25


# ------------------------------------------------- 8-device serving


CHILD_SERVE = r"""
import numpy as np, jax
assert len(jax.devices()) == 8, jax.devices()
from repro.api import Solver
from repro.core import dijkstra_reference
from repro.graph import rmat1, graph_fingerprint
from repro.serve import (EdgeUpdate, LandmarkIndex, Query, Router,
                         SolutionCache, UpdateFeed)

g = rmat1(9, seed=5)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
solver = Solver("delta:5+threadq/a2a", mesh=mesh)
lm = LandmarkIndex(solver, g, k=4, symmetric=True)
router = Router(solver, g, cache=SolutionCache(byte_budget=64 << 20),
                landmarks=lm, max_batch=8)

rng = np.random.default_rng(0)
srcs = np.minimum(rng.zipf(1.3, size=100) - 1, g.n - 1)
queries = []
for i, s in enumerate(srcs):
    if i % 10 == 9:
        queries.append(Query(int(s), target=int(rng.integers(0, g.n)),
                             exact=False))
    elif i % 3 == 2:
        queries.append(Query(int(s), target=int(rng.integers(0, g.n))))
    else:
        queries.append(Query(int(s)))
answers = router.serve(queries)
assert len(answers) == 100 and all(a.query is q for a, q in
                                   zip(answers, queries))
refs = {}
for a in answers:
    s = a.query.source
    if s not in refs:
        refs[s] = dijkstra_reference(g, s)
    if a.served_by == "landmark":
        d = refs[s][a.query.target]
        assert a.lower <= d <= a.upper or (
            np.isinf(d) and np.isinf(a.upper)), (a.query, d)
    elif a.query.target is not None:
        r = refs[s][a.query.target]
        assert a.distance == r or (np.isinf(a.distance) and np.isinf(r))
    else:
        assert np.allclose(np.where(np.isinf(refs[s]), -1, refs[s]),
                           np.where(np.isinf(a.solution.state), -1,
                                    a.solution.state))
assert router.cache.stats.hit_rate() > 0.2, router.cache.stats

# streamed improving update keeps answers fresh via warm restarts
feed = UpdateFeed(g, solver, cache=router.cache, landmarks=lm)
e = int(rng.integers(0, g.m))
res = feed.apply(EdgeUpdate(int(g.src[e]), int(g.dst[e]),
                            float(g.weight[e]) * 0.25))
assert res.improving and res.warm_refreshes > 0
from repro.api import Problem, SingleSource
for key, sol in router.cache.entries_for(graph_fingerprint(g))[:3]:
    cold = solver.solve(Problem(g, SingleSource(key[1])))
    assert np.array_equal(sol.state, cold.state), key[1]
print('SERVE-MULTIDEV-OK')
"""


@pytest.mark.slow
def test_router_8_devices_mixed_queries():
    """100 mixed queries through the router on an 8-device mesh, plus
    a streamed improving update with warm-refresh verification."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", CHILD_SERVE], env=env,
        capture_output=True, text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SERVE-MULTIDEV-OK" in r.stdout
