"""Observability driver: record a traced solve, export flight records,
summarize convergence.

    # record: traced vs untraced solve, bit-identity + overhead gate,
    # Perfetto + JSONL + Prometheus artifacts (CI's obs job)
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.obs record \
        --graph rmat1 --scale 9 --spec "delta:5/sparse" \
        --trace-json TRACE_solve.json --jsonl FLIGHT_solve.jsonl \
        --metrics OBS_metrics.txt --gate 1.15

    # export: JSONL flight record -> Chrome-trace/Perfetto JSON
    PYTHONPATH=src python -m repro.launch.obs export \
        FLIGHT_solve.jsonl --out TRACE_solve.json

    # summarize: per-superstep convergence table from a flight record
    PYTHONPATH=src python -m repro.launch.obs summarize FLIGHT_solve.jsonl

``record`` solves the same problem twice — once untraced, once with
``/trace`` — and machine-checks the tentpole claims: final state and
``WorkMetrics`` bit-identical, per-superstep sums reconciling exactly
with the aggregate metrics, and traced wall time within ``--gate``
(default 1.15x) of untraced (min over ``--repeats``, compile warmed
out of both sides).  Load the Perfetto JSON at https://ui.perfetto.dev
or chrome://tracing.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time


def _load_flight(path: str):
    """Rebuild (Tracer, [SolveTrace]) from a JSONL flight record."""
    from repro.obs import SolveTrace, Tracer
    from repro.obs.trace import Event, Span

    tracer = Tracer()
    traces: dict[str, SolveTrace] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            kind = rec.pop("kind")
            if kind == "span":
                tracer.spans.append(Span(**rec))
            elif kind == "event":
                tracer.events.append(Event(**rec))
            elif kind == "solve":
                tr = SolveTrace(**rec)
                traces[tr.config_name] = tr
            # superstep rows are redundant with the solve header (they
            # exist for line-oriented tooling); skip on reload
    return tracer, list(traces.values())


def cmd_record(args) -> int:
    import numpy as np

    from repro.api import Problem, SingleSource, Solver
    from repro.launch.mesh import make_local_topology
    from repro.launch.sssp import build_graph
    from repro.obs import (
        MetricsRegistry, Tracer, use_tracer,
        write_chrome_trace, write_flight_jsonl,
    )

    g = build_graph(args.graph, args.scale, args.seed)
    topo = make_local_topology()
    base = Solver(args.spec, mesh=topo.mesh)
    if base.config.trace:
        print("error: pass the UNTRACED spec; record adds /trace itself",
              file=sys.stderr)
        return 2
    traced_cfg = dataclasses.replace(
        base.config, trace=True, adapt_window=args.window
    )
    traced = Solver(traced_cfg, mesh=base.mesh)
    prob = Problem(g, SingleSource(args.source))
    print(f"[obs] {g.name}: n={g.n} m={g.m} spec={base.config.name} "
          f"devices={base.n_devices} window={args.window}")

    def timed(solver):
        best, sol = float("inf"), None
        for _ in range(max(1, args.repeats)):
            t0 = time.perf_counter()
            sol = solver.solve(prob)
            best = min(best, time.perf_counter() - t0)
        return best, sol

    # warm both engines (compile + partition) outside the timed window
    base.solve(prob)
    traced.solve(prob)

    wall_base, sol_base = timed(base)
    registry = MetricsRegistry()
    tracer = Tracer(registry=registry)
    with use_tracer(tracer):
        wall_traced, sol_traced = timed(traced)

    # -- the tentpole claims, machine-checked -------------------------
    assert np.array_equal(sol_base.state, sol_traced.state), \
        "traced solve diverged from untraced state"
    assert sol_base.metrics == sol_traced.metrics, (
        f"traced metrics differ:\n  untraced {sol_base.metrics}\n"
        f"  traced   {sol_traced.metrics}")
    tr = sol_traced.trace
    assert tr is not None
    tr.reconcile(sol_traced.metrics)
    print("[obs] bit-identity: state EQUAL, metrics EQUAL, "
          "trace sums reconcile")
    print(f"[obs] untraced {sol_base.metrics}")

    ratio = wall_traced / wall_base if wall_base > 0 else 1.0
    print(f"[obs] wall: untraced {wall_base*1e3:.1f}ms, traced "
          f"{wall_traced*1e3:.1f}ms ({ratio:.2f}x, gate {args.gate}x, "
          f"min of {args.repeats})")

    if args.table:
        print(tr.table())
    if args.trace_json:
        write_chrome_trace(args.trace_json, tracer, [tr])
        print(f"[obs] wrote Perfetto trace: {args.trace_json} "
              f"({len(tracer.spans)} spans, {len(tracer.events)} events)")
    if args.jsonl:
        write_flight_jsonl(args.jsonl, tracer, [tr])
        print(f"[obs] wrote flight record: {args.jsonl}")
    if args.metrics:
        with open(args.metrics, "w") as f:
            f.write(registry.expose())
        print(f"[obs] wrote exposition: {args.metrics}")

    if args.gate and ratio > args.gate:
        print(f"[obs] FAIL: traced/untraced {ratio:.2f}x exceeds the "
              f"{args.gate}x overhead gate", file=sys.stderr)
        return 1
    return 0


def cmd_export(args) -> int:
    from repro.obs import write_chrome_trace

    tracer, traces = _load_flight(args.record)
    write_chrome_trace(args.out, tracer, traces)
    print(f"[obs] {args.record} -> {args.out} ({len(tracer.spans)} "
          f"spans, {len(tracer.events)} events, {len(traces)} solves)")
    return 0


def cmd_summarize(args) -> int:
    _, traces = _load_flight(args.record)
    if not traces:
        print("no solve traces in record", file=sys.stderr)
        return 1
    for tr in traces:
        print(f"[obs] {tr.config_name}: n={tr.n}")
        print(tr.table())
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    rec = sub.add_parser("record", help="traced vs untraced solve with "
                         "bit-identity assertions and overhead gate")
    rec.add_argument("--graph", default="rmat1",
                     choices=["rmat1", "rmat2", "road", "smallworld"])
    rec.add_argument("--scale", type=int, default=9)
    rec.add_argument("--spec", default="delta:5/sparse")
    rec.add_argument("--source", type=int, default=0)
    rec.add_argument("--seed", type=int, default=0)
    rec.add_argument("--window", type=int, default=8,
                     help="supersteps per recorder segment (larger = "
                          "fewer host syncs = lower overhead)")
    rec.add_argument("--repeats", type=int, default=3,
                     help="timing repeats; the gate compares minima")
    rec.add_argument("--gate", type=float, default=1.15,
                     help="max traced/untraced wall ratio (0 disables)")
    rec.add_argument("--trace-json", default=None,
                     help="write Chrome-trace/Perfetto JSON here")
    rec.add_argument("--jsonl", default=None,
                     help="write the JSONL flight record here")
    rec.add_argument("--metrics", default=None,
                     help="write Prometheus text exposition here")
    rec.add_argument("--table", action="store_true",
                     help="print the per-superstep convergence table")
    rec.set_defaults(fn=cmd_record)

    exp = sub.add_parser("export", help="JSONL flight record -> "
                         "Chrome-trace/Perfetto JSON")
    exp.add_argument("record", help="JSONL flight record path")
    exp.add_argument("--out", default="TRACE_solve.json")
    exp.set_defaults(fn=cmd_export)

    summ = sub.add_parser("summarize", help="per-superstep table from "
                          "a JSONL flight record")
    summ.add_argument("record", help="JSONL flight record path")
    summ.set_defaults(fn=cmd_summarize)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    # must be set before jax initializes; harmless if already set
    os.environ.setdefault("XLA_FLAGS", "")
    sys.exit(main())
