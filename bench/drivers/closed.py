"""A closed loop of single-source solves: one caller runs
``Solver.solve(Problem(g, SingleSource(key)))`` back to back.

The keys are Graph500 search keys, vertices of degree at least one,
distinct, in an order drawn from ``--seed``.  Solves start until
``seconds`` have passed; the solve in progress then finishes and
counts.  Every answer of the window is compared with the reference.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import graph500
from bench.harness import Check, Solve, Window, failed_solves, say
from bench.reference import mismatches


def prepare(s, mix: dict, seed: int) -> np.ndarray:
    """The window's keys, drawn from ``seed``, and one warm-up solve
    through the window's own entry from the vertex of least degree:
    the same compiled program, with a solve that ends in a superstep
    or two."""
    from repro.api import Problem, SingleSource

    keys = graph500.search_keys(s.src, s.n, seed)
    warm = int(np.argmin(np.bincount(s.src, minlength=s.n)))
    s.solver.solve(Problem(s.graph, SingleSource(warm)))
    return keys


def drive(s, keys: np.ndarray, seconds: float, watch, span=None) -> Window:
    from repro.api import Problem, SingleSource

    span = span or (lambda name: contextlib.nullcontext())
    solves, answers = [], []
    watch.start()
    with span("bench.window"):
        t0 = time.perf_counter()
        while not solves or time.perf_counter() - t0 < seconds:
            key = int(keys[len(solves) % len(keys)])
            rec = Solve(key, time.perf_counter(), 0.0)
            state = None
            try:
                with span("bench.solve"):
                    sol = s.solver.solve(Problem(s.graph, SingleSource(key)))
                rec.supersteps = int(sol.metrics.supersteps)
                rec.converged = bool(sol.metrics.converged)
                state = sol.state
            except Exception as e:  # a failed solve is counted, not fatal
                rec.error = f"{type(e).__name__}: {e}"
            rec.t1 = time.perf_counter()
            solves.append(rec)
            answers.append(state)
    return Window(solves, t0, solves[-1].t1, watch.count, answers)


def check(s, win: Window, ref) -> tuple[list, dict]:
    """Every answer against the reference's distances from its key
    (computed once a key).  Returns the checks and, per solve, its
    reach (edges, vertices)."""
    dist, bad = {}, {}
    for rec, state in zip(win.solves, win.answers):
        if state is None:
            continue
        if rec.key not in dist:
            dist[rec.key] = ref.distances(rec.key)
        bad.setdefault(rec.key, []).append(mismatches(state, dist[rec.key]))
    for key, counts in bad.items():
        d = dist[key]
        say(f"check key={key} answers={len(counts)} "
            f"mismatched_worst={max(counts)} "
            f"max_distance={d[np.isfinite(d)].max()}")
    checks = [
        Check("mismatched_vertices",
              max((max(c) for c in bad.values()), default=0), 0),
        Check("failed_solves", failed_solves(win), 0),
    ]
    return checks, {i: ref.reach(r.key) for i, r in enumerate(win.solves)}
