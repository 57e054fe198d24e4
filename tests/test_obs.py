"""repro.obs: span tracer, /trace flight recorder, exporters, and the
serving metrics surface.

The tentpole gate is the bit-identity grid: a ``/trace`` solve runs
through the segment engine purely to publish per-superstep windows, so
its final state AND its WorkMetrics must equal the untraced solve's
exactly, and the per-superstep sums must reconcile with the aggregate.
The 8-device version runs in a subprocess (marked slow) like the other
multi-device coverage.
"""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from repro.api import Problem, SingleSource, Solver, SolverConfig
from repro.core.metrics import LatencyStats, WorkMetrics
from repro.obs import (
    FlightRecorder, MetricsRegistry, SolveTrace, Tracer,
    chrome_trace, flight_jsonl, serve_metrics, use_tracer,
)
from repro.obs import trace as obs


# ------------------------------------------------------------- tracer


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_tracer_nesting_and_injected_clock():
    tr = Tracer(clock=FakeClock())
    with use_tracer(tr):
        with obs.span("outer", a=1) as sp:
            obs.event("tick", k=2)
            with obs.span("inner"):
                pass
            sp.set(b=3)
    # clock: outer.t0=1, event=2, inner.t0=3, inner.t1=4, outer.t1=5
    inner, outer = tr.spans  # inner closes first
    assert inner.name == "inner" and outer.name == "outer"
    assert (outer.t0, outer.t1) == (1.0, 5.0) and outer.duration_s == 4.0
    assert (inner.t0, inner.t1) == (3.0, 4.0)
    assert inner.parent_id == outer.span_id and outer.parent_id is None
    assert outer.attrs == {"a": 1, "b": 3}
    ev, = tr.events
    assert ev.t == 2.0 and ev.span_id == outer.span_id
    assert tr.children_of(outer.span_id) == [inner]


def test_tracer_off_is_noop():
    assert obs.current_tracer() is None
    s1 = obs.span("anything", x=1)
    s2 = obs.span("else")
    assert s1 is s2  # shared no-op handle: zero allocation when off
    with s1 as sp:
        sp.set(ignored=True)
    obs.event("nothing")  # no tracer — must not raise


def test_tracer_error_attr_and_use_tracer_restores():
    tr = Tracer()
    prev = obs.current_tracer()
    with use_tracer(tr):
        with pytest.raises(RuntimeError):
            with obs.span("boom"):
                raise RuntimeError("x")
    assert obs.current_tracer() is prev
    assert tr.find("boom")[0].attrs["error"] == "RuntimeError"


def test_tracer_bounded_drops_counted():
    tr = Tracer(max_records=3)
    with use_tracer(tr):
        for _ in range(5):
            obs.event("e")
        with obs.span("s"):
            pass
    assert len(tr.events) == 3 and len(tr.spans) == 0
    assert tr.dropped == 3
    with pytest.raises(ValueError):
        Tracer(max_records=0)


def test_tracer_feeds_registry():
    reg = MetricsRegistry()
    tr = Tracer(clock=FakeClock(), registry=reg)
    with use_tracer(tr):
        with obs.span("work"):
            obs.event("hit")
        obs.event("hit")
    text = reg.expose()
    assert 'repro_events_total{event="hit"} 2' in text
    assert 'repro_span_seconds_count{span="work"} 1' in text
    # FakeClock ticks: span.t0=1, event=2, span.t1=3 -> duration 2
    assert 'repro_span_seconds_sum{span="work"} 2' in text


# ----------------------------------------------------------- registry


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("c_total", help="h", labels={"k": "v"})
    c.inc()
    c.inc(2)
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g", help="h")
    g.set(4.5)
    live = reg.gauge("g_live", help="h", fn=lambda: 7)
    h = reg.histogram("h_seconds", help="h", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.expose()
    assert 'c_total{k="v"} 3' in text
    assert "# TYPE c_total counter" in text
    assert "g 4.5" in text
    assert "g_live 7" in text
    assert 'h_seconds_bucket{le="0.1"} 1' in text
    assert 'h_seconds_bucket{le="1"} 2' in text  # 1.0 renders as "1"
    assert 'h_seconds_bucket{le="+Inf"} 3' in text
    assert "h_seconds_count 3" in text
    assert live is reg.gauge("g_live", help="h")  # get-or-create


def test_registry_same_name_distinct_labels_and_kind_conflict():
    reg = MetricsRegistry()
    a = reg.counter("n_total", help="h", labels={"x": "1"})
    b = reg.counter("n_total", help="h", labels={"x": "2"})
    assert a is not b
    a.inc()
    assert 'n_total{x="1"} 1' in reg.expose()
    with pytest.raises(ValueError):
        reg.gauge("n_total", help="h")


# ---------------------------------------------------------- exporters


def _tiny_trace():
    tr = Tracer(clock=FakeClock())
    with use_tracer(tr):
        with obs.span("solve", spec="s"):
            obs.event("cache_miss")
    st = SolveTrace(config_name="s", n=8, rows_per_rank=8,
                    sparse_capable=True,
                    pending=[4, 2, 0], eligible=[4, 2, 1],
                    rows=[4, 2, 1], sparse_used=[1, 0, 1],
                    bytes_moved=[0, 64, 0],
                    segments=[{"segment": 0, "supersteps": 3,
                               "t0": 1.0, "t1": 2.0}])
    return tr, st


def test_chrome_trace_shapes():
    tr, st = _tiny_trace()
    doc = chrome_trace(tr, [st])
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and xs[0]["name"] == "solve" and xs[0]["dur"] > 0
    assert any(e["ph"] == "i" and e["name"] == "cache_miss" for e in evs)
    counters = [e for e in evs if e["ph"] == "C"]
    # 3 supersteps × (frontier + bytes) counter samples
    assert sum("frontier" in e["name"] for e in counters) == 3
    assert sum("bytes" in e["name"] for e in counters) == 3
    json.dumps(doc)  # must be serializable as-is


def test_flight_jsonl_kinds():
    tr, st = _tiny_trace()
    lines = [json.loads(ln) for ln in flight_jsonl(tr, [st])]
    kinds = {ln["kind"] for ln in lines}
    assert kinds == {"solve", "superstep", "span", "event"}
    assert sum(ln["kind"] == "superstep" for ln in lines) == 3
    solve = next(ln for ln in lines if ln["kind"] == "solve")
    rebuilt = SolveTrace(**{k: v for k, v in solve.items()
                            if k != "kind"})
    assert rebuilt.pending == st.pending
    assert rebuilt.total_bytes() == st.total_bytes()


def test_serve_metrics_http():
    reg = MetricsRegistry()
    reg.counter("up_total", help="h").inc()
    server = serve_metrics(reg, port=0)
    try:
        host, port = server.server_address[0], server.server_address[1]
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10) as r:
            body = r.read().decode()
        assert "up_total 1" in body
        with urllib.request.urlopen(
                f"http://{host}:{port}/stats", timeout=10) as r:
            stats = json.loads(r.read().decode())
        assert stats["up_total"]["type"] == "counter"
        assert stats["up_total"]["samples"][0]["value"] == 1
    finally:
        server.shutdown()


# --------------------------------------------- /trace spec grammar


def test_trace_spec_grammar():
    c = SolverConfig.from_spec("delta:5/sparse/trace")
    assert c.trace and c.adapt is None and c.name.endswith("/trace")
    assert SolverConfig.from_spec(c.name) == c  # round-trip
    assert c.engine_config("sssp").adapt_window == c.adapt_window > 0
    # untraced spec keeps the unsegmented engine
    base = SolverConfig.from_spec("delta:5/sparse")
    assert base.engine_config("sssp").adapt_window == 0
    with pytest.raises(ValueError, match="duplicate trace"):
        SolverConfig.from_spec("delta:5/trace/trace")
    with pytest.raises(ValueError, match="takes no argument"):
        SolverConfig.from_spec("delta:5/trace:4")
    with pytest.raises(ValueError, match="repair loop"):
        SolverConfig.from_spec("delta:5/sparse/q:bf16/trace")
    # /adapt composition: one segmentation serves both
    both = SolverConfig.from_spec("delta:5/sparse/adapt:rho/trace")
    assert both.trace and both.adapt == "rho"


# ------------------------------------------- bit-identity grid


GRID_SPECS = [
    "chaotic",
    "dijkstra",
    "delta:5",
    "delta:5+nodeq",
    "delta:20+threadq",
    "kla:2",
    "delta:5 > chunk:delta:1",
]


@pytest.mark.parametrize("exchange", ["a2a", "sparse"])
@pytest.mark.parametrize("root", GRID_SPECS)
def test_trace_bit_identity(root, exchange, tiny_graphs):
    """A /trace solve must be bit-identical to the untraced solve —
    state AND metrics — and its per-superstep sums must reconcile
    exactly with the aggregate."""
    g = tiny_graphs[0]
    prob = Problem(g, SingleSource(0))
    base = Solver(f"{root}/{exchange}").solve(prob)
    traced = Solver(f"{root}/{exchange}/trace").solve(prob)
    assert np.array_equal(base.state, traced.state)
    assert base.metrics == traced.metrics
    tr = traced.trace
    assert tr is not None and base.trace is None
    tr.reconcile(traced.metrics)
    assert tr.supersteps == traced.metrics.supersteps
    assert tr.segments
    assert tr.config_name == traced.config.name


def test_trace_all_graphs(tiny_graphs):
    """One spec across every fixture graph shape."""
    for g in tiny_graphs:
        prob = Problem(g, SingleSource(1))
        base = Solver("delta:5/sparse").solve(prob)
        traced = Solver("delta:5/sparse/trace").solve(prob)
        assert np.array_equal(base.state, traced.state), g.name
        assert base.metrics == traced.metrics, g.name
        traced.trace.reconcile(traced.metrics)


def test_trace_segments_cover_supersteps(tiny_graphs):
    sol = Solver("delta:5/sparse/trace").solve(
        Problem(tiny_graphs[0], SingleSource(0)))
    tr = sol.trace
    assert sum(s["supersteps"] for s in tr.segments) == tr.supersteps
    assert all(s["t1"] >= s["t0"] for s in tr.segments)
    assert tr.pending[-1] == 0  # converged
    # table renders one row per superstep plus header/footer
    lines = tr.table().splitlines()
    assert len(lines) == tr.supersteps + 3


def test_trace_batch_rejected(tiny_graphs):
    s = Solver("delta:5/sparse/trace")
    probs = [Problem(tiny_graphs[0], SingleSource(i)) for i in (0, 1)]
    with pytest.raises(ValueError, match="flight recorder"):
        s.solve_batch(probs)


def test_trace_resolve_counts_host_sweep(tiny_graphs):
    """resolve()'s host bootstrap sweep has no engine window; the trace
    counts it so the superstep balance stays exact."""
    import copy

    g = copy.deepcopy(tiny_graphs[0])
    s = Solver("delta:5/sparse/trace")
    sol = s.solve(Problem(g, SingleSource(0)))
    g.weight[:] = np.minimum(g.weight, np.float32(0.5))  # improving
    sol2 = s.resolve(sol, graph=g)
    assert sol2.trace is not None
    assert sol2.trace.host_sweeps == 1
    sol2.trace.reconcile(sol2.metrics)
    cold = Solver("delta:5/sparse").solve(Problem(g, SingleSource(0)))
    assert np.array_equal(sol2.state, cold.state)


def test_trace_reconcile_catches_mismatch():
    tr = SolveTrace(pending=[2, 0], eligible=[2, 1], rows=[2, 1],
                    sparse_used=[1, 1], bytes_moved=[0, 0],
                    sparse_capable=True)
    m = WorkMetrics(supersteps=2, commits=5, exchange_bytes=0)
    with pytest.raises(AssertionError, match="commits"):
        tr.reconcile(m)  # Σeligible is 3, not 5
    m = WorkMetrics(supersteps=5, commits=3)
    with pytest.raises(AssertionError, match="supersteps"):
        tr.reconcile(m)


def test_recorder_accumulates_segments():
    from repro.core.metrics import SuperstepWindow

    rec = FlightRecorder("spec")
    w = SuperstepWindow(pending=[3, 1], eligible=[2, 2], rows=[2, 2],
                        sparse_used=[1, 0], bytes_moved=[8, 16],
                        overflow_streak=0, supersteps_total=2, n=16,
                        rows_per_rank=16, sparse_capable=True)
    rec.on_window(w, {"supersteps": 2, "t0": 1.0, "t1": 2.0})
    rec.on_window(w)
    tr = rec.finish(WorkMetrics())
    assert tr.supersteps == 4 and tr.total_bytes() == 48
    assert [s["segment"] for s in tr.segments] == [0, 1]
    assert tr.segments[1]["t1"] >= tr.segments[1]["t0"]


# -------------------------------------------------- solver spans


def test_solver_solve_emits_spans(tiny_graphs):
    tr = Tracer()
    s = Solver("delta:5/sparse/trace")
    with use_tracer(tr):
        sol = s.solve(Problem(tiny_graphs[0], SingleSource(0)))
    solve_span, = tr.find("solver.solve")
    assert solve_span.attrs["supersteps"] == sol.metrics.supersteps
    assert solve_span.attrs["converged"] is True
    assert tr.find("solver.partition")
    segs = tr.find("tune.segment")
    assert len(segs) == len(sol.trace.segments)
    assert all(sp.parent_id is not None for sp in segs)
    names = {e.name for e in tr.events}
    assert "engine_cache_miss" in names or "engine_cache_hit" in names
    # the host steps of the solve, each a child of it
    for name in ("solver.fingerprint", "solver.initial_state",
                 "solver.unpermute"):
        sp, = tr.find(name)
        assert sp.parent_id == solve_span.span_id
    # the static engine's call: dispatch through the result on the host
    with use_tracer(tr):
        Solver("delta:5/sparse").solve(Problem(tiny_graphs[0], SingleSource(0)))
    engine, = tr.find("solver.engine")
    assert tr.find("solver.solve")[-1].span_id == engine.parent_id


def test_solver_spans_reach_the_profiler(tiny_graphs, tmp_path):
    """With no Tracer installed, a profiler session sees the solve's
    spans as annotations, nested on the thread that solved."""
    import jax

    from bench import xplane

    s = Solver("delta:5/sparse")
    problem = Problem(tiny_graphs[0], SingleSource(0))
    s.solve(problem)  # compiled outside the trace
    assert obs.current_tracer() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        s.solve(problem)
    finally:
        jax.profiler.stop_trace()
    pb, = tmp_path.glob("**/*.xplane.pb")
    lines = [ln for p in xplane.load(pb).planes for ln in p.lines
             if any(e.name == "solver.solve" for e in ln.events)]
    assert len(lines) == 1
    spans = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
             for e in lines[0].events if e.name.startswith("solver.")}
    lo, hi = spans["solver.solve"]
    for name in ("solver.fingerprint", "solver.initial_state",
                 "solver.engine", "solver.unpermute"):
        s0, s1 = spans[name]
        assert lo <= s0 <= s1 <= hi
    order = sorted(spans, key=spans.get)
    assert order[1:] == ["solver.fingerprint", "solver.initial_state",
                         "solver.engine", "solver.unpermute"]


def test_spec_check_trace_rules():
    from repro.analyze.spec_check import check_config

    fs = check_config("delta:5/sparse/trace")
    rules = {f.rule for f in fs}
    assert "trace-no-batch" in rules
    assert "trace-adapt-composition" not in rules
    fs = check_config("delta:5/sparse/adapt:rho/trace")
    assert any(f.rule == "trace-adapt-composition" and f.severity == "warn"
               for f in fs)
    fs = check_config(SolverConfig.from_spec("delta:5/sparse/trace",
                                             collect_metrics=False))
    assert any(f.rule == "trace-forces-metrics" for f in fs)


# --------------------------------------- serving tier observability


def test_router_latency_ring_and_evictions(tiny_graphs):
    from repro.serve import Router

    g = tiny_graphs[0]
    r = Router(Solver("delta:5/sparse"), g, latency_window=4)
    for ms in (1, 2, 3, 4, 5, 6):
        r._record_latency(ms / 1e3)
    assert r.stats.latency_evictions == 2
    st = r.latency_stats()
    assert st.count == 4
    assert st.min_s == pytest.approx(0.003)
    assert st.max_s == pytest.approx(0.006)
    with pytest.raises(ValueError, match="latency_window"):
        Router(Solver("delta:5/sparse"), g, latency_window=0)


def test_router_flush_span_carries_qids(tiny_graphs):
    from repro.serve import Query, Router

    g = tiny_graphs[0]
    tr = Tracer()
    router = Router(Solver("delta:5/sparse"), g, max_batch=4)
    with use_tracer(tr):
        t1 = router.submit(Query(0))
        t2 = router.submit(Query(0, target=3))
        router.flush()
    assert (t1.qid, t2.qid) == (1, 2)
    flush, = tr.find("router.flush")
    assert flush.attrs["qids"] == [1, 2]
    assert flush.attrs["solved"] == 1  # deduped to one source
    submits = [e for e in tr.events if e.name == "router.submit"]
    assert [e.attrs["qid"] for e in submits] == [1, 2]
    assert any(e.name == "router.cache_fill" for e in tr.events)
    assert router.latency_stats().count == 2


# ---------------------------------------------------- metrics satellites


def test_workmetrics_str_shows_anomalies_only_when_nonzero():
    clean = str(WorkMetrics(supersteps=3, commits=2, relaxations=4))
    for field in ("sparse_fallbacks", "retraces", "repair_sweeps",
                  "overflow_streak"):
        assert field not in clean
    noisy = str(WorkMetrics(supersteps=3, sparse_fallbacks=2, retraces=1,
                            repair_sweeps=4, overflow_streak=5,
                            converged=False))
    assert "sparse_fallbacks=2" in noisy
    assert "retraces=1" in noisy
    assert "repair_sweeps=4" in noisy
    assert "overflow_streak=5" in noisy
    assert noisy.endswith("TRUNCATED")


def test_latency_stats_min_and_merge():
    a = LatencyStats.from_samples([0.001, 0.002, 0.003])
    b = LatencyStats.from_samples([0.010])
    assert a.min_s == 0.001 and b.min_s == 0.010
    m = a.merge(b)
    assert m.count == 4
    assert m.total_s == pytest.approx(0.016)
    assert m.mean_s == pytest.approx(0.004)
    assert m.min_s == 0.001 and m.max_s == 0.010
    # count-weighted percentile approximation
    assert m.p50_s == pytest.approx((a.p50_s * 3 + b.p50_s * 1) / 4)
    # empty windows merge to a copy, not a crash
    empty = LatencyStats()
    assert empty.merge(a) == a and a.merge(empty) == a


# ------------------------------------------------- 8-device subprocess


CHILD_OBS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.api import Problem, SingleSource, Solver
from repro.graph import rmat1
from repro.obs import Tracer, use_tracer

g = rmat1(9, seed=0)
prob = Problem(g, SingleSource(0))
for spec in ("delta:5/sparse", "delta:20+threadq/a2a"):
    base = Solver(spec).solve(prob)
    tracer = Tracer()
    with use_tracer(tracer):
        traced = Solver(spec + "/trace").solve(prob)
    assert np.array_equal(base.state, traced.state), spec
    assert base.metrics == traced.metrics, spec
    tr = traced.trace
    tr.reconcile(traced.metrics)
    assert tr.supersteps == traced.metrics.supersteps
    # multi-device: the dense/sparse byte accounting is live (P > 1)
    assert traced.metrics.exchange_bytes > 0, spec
    assert tr.total_bytes() == traced.metrics.exchange_bytes, spec
    assert tracer.find("solver.solve") and tracer.find("tune.segment")
print("OBS-MULTIDEV-OK")
"""


@pytest.mark.slow
def test_trace_bit_identity_8_devices():
    """The tentpole claim on a real 8-way mesh: traced state, metrics,
    and per-superstep byte sums all match the untraced solve."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", CHILD_OBS], env=env,
        capture_output=True, text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OBS-MULTIDEV-OK" in r.stdout
