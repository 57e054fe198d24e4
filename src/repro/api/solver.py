"""Compile-once / solve-many solver facade.

The paper's thesis is one fixed machine (the self-stabilizing kernel +
EAGM engine) fed many problems; the :class:`Solver` makes the API look
the same.  Engines are jitted once per (partition shape, mesh, config,
batch) and kept in a process-wide LRU cache, so serving a stream of
queries re-traces nothing:

    solver = Solver("delta:5+threadq/a2a")
    sol  = solver.solve(Problem(g, SingleSource(0)))
    sols = solver.solve_batch([Problem(g, SingleSource(v)) for v in vs])
    sol2 = solver.resolve(sol, graph=g_cheaper)   # warm restart

``resolve`` is the self-stabilization dividend (paper §II): the kernel
converges from *any* state that is pointwise no better than the new
fixpoint, so after a perturbation that only improves candidate states
(edge-weight decreases, new edges, added sources) the previous
solution is a valid warm start and stabilizes in a few supersteps
instead of a full solve.  For perturbations that can worsen the
optimum (weight increases, removed edges) the monotone engine cannot
raise committed state — cold-solve those.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import SolverConfig, as_config
from repro.api.problem import (
    ExplicitSources,
    Problem,
    as_source_spec,
    get_processing,
)
from repro.core.engine import (
    EngineConfig,
    initial_state,
    initial_state_batch,
    make_engine,
)
from repro.core.frontier import (
    frontier_caps,
    grow_frontier_cap,
    payload_plane_words,
)
from repro.core.metrics import WorkMetrics
from repro.core.processing import ProcessingFn
from repro.graph.formats import Graph, graph_fingerprint
from repro.graph.partition import PartitionedGraph, partition_graph
from repro.obs import trace as obs
from repro.obs.recorder import FlightRecorder, SolveTrace

# ---------------------------------------------------------------------
# process-wide engine cache (shared by every Solver and by the legacy
# run_distributed shim) + jit trace counter
# ---------------------------------------------------------------------

_ENGINE_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_ENGINE_CACHE_SIZE = 32
_TRACE_COUNT = [0]
_EVICTIONS = [0]
_ADAPT_RETRACES = [0]


def trace_count() -> int:
    """Total jit traces of facade engines this process — the
    compile-once tests assert it stays flat across repeat solves."""
    return _TRACE_COUNT[0]


def note_adapt_retrace() -> None:
    """Record one engine build forced by a shape-changing adaptive
    decision (a frontier_cap the solve had not used before).  Called by
    the :mod:`repro.tune` controller; surfaced via
    :func:`engine_cache_info` and ``Solution.metrics.retraces``."""
    _ADAPT_RETRACES[0] += 1


def engine_cache_clear() -> None:
    _ENGINE_CACHE.clear()


def engine_cache_info() -> dict:
    """Stats seam for the serving tier: size/capacity of the process-
    wide compiled-engine cache, the cumulative trace count, LRU
    evictions, and engine builds forced by shape-changing adaptive
    retuning decisions."""
    return dict(
        size=len(_ENGINE_CACHE),
        capacity=_ENGINE_CACHE_SIZE,
        traces=_TRACE_COUNT[0],
        evictions=_EVICTIONS[0],
        adapt_retraces=_ADAPT_RETRACES[0],
    )


def batch_bucket(b: int) -> int:
    """Round a batch size up to the next power of two.  ``solve_batch``
    pads problem batches to these buckets so a serving workload whose
    batch size jitters between flushes (7, 8, 5, ...) reuses at most
    log2(max_batch) compiled engines instead of tracing one per size."""
    if b < 1:
        raise ValueError(f"batch size must be positive: {b}")
    return 1 << (b - 1).bit_length()


def _bump_trace():
    _TRACE_COUNT[0] += 1


def compiled_engine(
    mesh,
    ecfg: EngineConfig,
    n_parts: int,
    n_local: int,
    batch: Optional[int] = None,
):
    """The compiled (jitted) engine for this (shape, mesh, config,
    batch) cell, built at most once per process."""
    key = (mesh, ecfg, n_parts, n_local, batch)
    try:
        fn = _ENGINE_CACHE[key]
        _ENGINE_CACHE.move_to_end(key)
        obs.event("engine_cache_hit", exchange=ecfg.exchange,
                  n_parts=n_parts, batch=batch)
        return fn
    except KeyError:
        pass
    obs.event("engine_cache_miss", exchange=ecfg.exchange,
              n_parts=n_parts, batch=batch)
    with obs.span("engine.build", exchange=ecfg.exchange,
                  n_parts=n_parts, n_local=n_local, batch=batch,
                  adapt_window=ecfg.adapt_window):
        fn = make_engine(
            dict(n_parts=n_parts, n_local=n_local),
            mesh,
            ecfg,
            batch=batch,
            trace_hook=_bump_trace,
        )
    _ENGINE_CACHE[key] = fn
    if len(_ENGINE_CACHE) > _ENGINE_CACHE_SIZE:
        _ENGINE_CACHE.popitem(last=False)
        _EVICTIONS[0] += 1
    return fn


# consecutive sparse-overflow supersteps before _finish_metrics emits
# the actionable frontier_cap RuntimeWarning (below this, occasional
# dense fallbacks are the capacity veto working as designed)
OVERFLOW_WARN_STREAK = 3

# hard cap on quantized-payload repair restarts (each restart strictly
# lowers some committed value, so this is a safety net, not a tuning
# knob — one or two sweeps repair everything in practice)
QUANT_REPAIR_MAX_SWEEPS = 25


def exchange_words(
    pg: PartitionedGraph, ecfg: EngineConfig, it: int, fallbacks: int
) -> int:
    """Exact exchange word count per device for ``it`` supersteps of
    which ``fallbacks`` took the dense path, in Python ints (the
    engine moves a statically known word count per superstep and
    branch, so no overflow-prone on-device accumulator is needed).
    Per device per superstep:

      a2a   (P-1)·n_local·planes words — the reduce-scatter sends
            (P-1)/P of the n_pad candidate array (+ KLA levels).
            NOTE the seed's formula multiplied before its integer
            division (`n_pad * 4 * (P-1) // P`), which is nonzero for
            P > 1 but obscured the per-rank intent; this form is
            explicit.
      pmin  2x a2a — a full-array ring all-reduce per combine.
      sparse (P-1)·payload_plane_words(S) words on sparse supersteps
            (exact: (idx, val) [+ level] planes; quantized: u32
            indices + packed 16-bit delta codes + the per-segment
            bound words — the dtype-parametrized accounting), dense
            a2a words on the `fallbacks` dense ones.

    The adaptive driver calls this per segment with that segment's
    ``frontier_cap``, so byte totals stay exact across cap growth.
    """
    use_level = ecfg.hierarchy.needs_level
    nplanes = 2 if use_level else 1
    P_, nl = pg.n_parts, pg.n_local
    dense_words = (P_ - 1) * nl * nplanes
    if ecfg.exchange == "pmin":
        return it * 2 * dense_words
    if ecfg.exchange == "a2a":
        return it * dense_words
    _, slot_cap = frontier_caps(
        pg.rows_per_rank, pg.width, nl, P_, ecfg.frontier_cap
    )
    sparse_words = (P_ - 1) * payload_plane_words(
        slot_cap, use_level, ecfg.payload
    )
    return (it - fallbacks) * sparse_words + fallbacks * dense_words


def _warn_metrics(
    m: WorkMetrics, ecfg: EngineConfig, pg: PartitionedGraph, active
) -> None:
    """Actionable RuntimeWarnings derived from a finished solve's
    metrics: truncation at max_iters, and a consecutive-sparse-
    overflow run long enough that the silent per-superstep dense
    fallback is costing real bandwidth."""
    import warnings

    if not m.converged:
        warnings.warn(
            f"engine hit max_iters={ecfg.max_iters} with {int(active)} "
            "pending workitems left — the returned state is truncated "
            "(monotone but not yet the fixpoint); raise max_iters or "
            "check Solution.metrics.converged",
            RuntimeWarning,
            stacklevel=4,
        )
    if (
        ecfg.exchange in ("sparse", "auto")
        and m.overflow_streak >= OVERFLOW_WARN_STREAK
    ):
        row_cap, slot_cap = frontier_caps(
            pg.rows_per_rank, pg.width, pg.n_local, pg.n_parts,
            ecfg.frontier_cap,
        )
        spec = f"{ecfg.hierarchy.name}/{ecfg.exchange}"
        warnings.warn(
            f"sparse exchange capacity overflowed on "
            f"{m.overflow_streak} consecutive supersteps (spec "
            f"{spec!r}: row_cap={row_cap}, slot_cap={slot_cap}), each "
            "falling back to the dense relax or exchange; raise "
            f"frontier_cap (try "
            f"{grow_frontier_cap(pg.rows_per_rank, row_cap)}) or "
            "solve with /adapt:rho for automatic cap growth",
            RuntimeWarning,
            stacklevel=4,
        )


def _finish_metrics(
    pg: PartitionedGraph,
    ecfg: EngineConfig,
    it,
    commits,
    relax,
    classes,
    active=None,
    fallbacks=0,
    overflow_streak=0,
    push_chunks=0,
    exchange_slots=0,
    exchange_local=0,
) -> WorkMetrics:
    it = int(it)
    fallbacks = int(fallbacks)
    converged = True if active is None else int(active) == 0
    m = WorkMetrics(
        classes=int(classes),
        commits=int(commits),
        relaxations=int(relax),
        supersteps=it,
        workitems=int(commits),
        converged=converged,
        sparse_fallbacks=fallbacks,
        overflow_streak=int(overflow_streak),
        push_chunks=int(push_chunks),
        exchange_slots=int(exchange_slots),
        exchange_local=int(exchange_local),
    )
    m.exchange_bytes = exchange_words(pg, ecfg, it, fallbacks) * 4 * pg.n_parts
    m.collective_rounds = it * (
        (3 if ecfg.collect_metrics else 2)
        + (1 if ecfg.exchange in ("sparse", "auto") else 0)
    )
    _warn_metrics(m, ecfg, pg, active)
    return m


def solve_with_engine_config(
    pg: PartitionedGraph, mesh, ecfg: EngineConfig, sources: list[tuple]
) -> tuple[np.ndarray, WorkMetrics]:
    """Low-level entry with the legacy ``run_distributed`` signature;
    shares the facade's engine cache."""
    fn = compiled_engine(mesh, ecfg, pg.n_parts, pg.n_local)
    D0, T0, L0 = initial_state(pg, ecfg.processing, sources)
    D, *rest = fn(*pg.on_mesh(mesh), D0, T0, L0)
    m = _finish_metrics(pg, ecfg, *rest)
    return pg.unpermute(np.asarray(D).reshape(-1)), m


# ---------------------------------------------------------------------
# Solution + Solver
# ---------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class Solution:
    """Result of one query: the committed state (in original vertex
    ids) plus what ``resolve`` needs to warm-restart from it
    (``padded`` is in the partition's relabeled slot space, so the
    producing :class:`PartitionedGraph` rides along for the layout-
    compatibility check)."""

    state: np.ndarray          # (n,) committed per-vertex state
    metrics: WorkMetrics
    problem: Problem
    config: SolverConfig
    padded: np.ndarray         # (P, n_local) committed state, padded
    pg: Optional[PartitionedGraph] = None
    # per-superstep flight record (config.trace / '/trace' specs only)
    trace: Optional[SolveTrace] = None

    @property
    def graph(self):
        return self.problem.graph

    @property
    def source(self) -> Optional[int]:
        """The single source vertex, if this solution has exactly one
        (the serving tier's cache key); None for multi-source/CC."""
        items = self.problem.source_items()
        if len(items) == 1:
            return int(items[0][0])
        return None

    @property
    def nbytes(self) -> int:
        """Resident bytes of this solution's state arrays — the unit
        the serving tier's byte-budget cache accounts in."""
        return int(self.state.nbytes) + int(self.padded.nbytes)

    def distance_to(self, v: int) -> float:
        """Committed state at vertex ``v`` (for SSSP: the distance
        source → v) — the point-to-point read the router serves."""
        if not 0 <= int(v) < self.state.shape[0]:
            raise ValueError(
                f"vertex {v} outside [0, {self.state.shape[0]})"
            )
        return float(self.state[int(v)])


class Solver:
    """Compile-once / solve-many facade over the distributed EAGM
    engine.  One Solver = one (mesh, SolverConfig); problems supply
    graph + sources + processing.  Raw :class:`Graph` inputs are
    partitioned over the mesh once and memoized."""

    def __init__(
        self,
        config: Union[str, SolverConfig, None] = None,
        mesh=None,
    ):
        self.config = as_config(config)
        if mesh is None:
            mesh = jax.make_mesh((jax.device_count(),), ("data",))
        self.mesh = mesh
        self.n_devices = int(np.prod(tuple(mesh.devices.shape)))
        # id(graph) -> (graph, fingerprint, PartitionedGraph); bounded
        # LRU so a stream of distinct graphs can't grow it unboundedly
        self._pg_cache: "OrderedDict[int, tuple]" = OrderedDict()
        self._pg_cache_size = 8
        # adaptive-solve counters (config.adapt specs only)
        self._adapt_stats = dict(
            solves=0, segments=0, retraces=0, cap_growths=0
        )

    # -- graph handling ------------------------------------------------

    def partition(self, graph: Union[Graph, PartitionedGraph]) -> PartitionedGraph:
        if isinstance(graph, PartitionedGraph):
            if graph.n_parts != self.n_devices:
                raise ValueError(
                    f"graph partitioned for {graph.n_parts} parts but "
                    f"mesh has {self.n_devices} devices"
                )
            if graph.partitioner != self.config.partition:
                raise ValueError(
                    f"graph pre-partitioned with "
                    f"{graph.partitioner!r} but config requests "
                    f"{self.config.partition!r}; re-partition with "
                    "repro.graph.partition_graph or pass the raw Graph"
                )
            return graph
        with obs.span("solver.fingerprint", n=graph.n, m=graph.m):
            fp = graph_fingerprint(graph)
        hit = self._pg_cache.get(id(graph))
        if hit is not None and hit[0] is graph and hit[1] == fp:
            self._pg_cache.move_to_end(id(graph))
            obs.event("partition_memo_hit", n=graph.n)
            return hit[2]
        with obs.span("solver.partition", n=graph.n, m=graph.m,
                      partitioner=self.config.partition,
                      n_parts=self.n_devices):
            pg = partition_graph(
                graph, self.n_devices, partitioner=self.config.partition
            )
            pg.on_mesh(self.mesh)  # the graph's one host-to-device copy
        self._pg_cache[id(graph)] = (graph, fp, pg)
        if len(self._pg_cache) > self._pg_cache_size:
            self._pg_cache.popitem(last=False)
        return pg

    def stats(self) -> dict:
        """Serving-tier observability: this solver's partition-memo
        occupancy plus the process-wide engine-cache stats."""
        return dict(
            partition_memo_size=len(self._pg_cache),
            partition_memo_capacity=self._pg_cache_size,
            engine_cache=engine_cache_info(),
            adapt=dict(self._adapt_stats),
        )

    # -- engine access -------------------------------------------------

    def compiled(
        self,
        n_parts: int,
        n_local: int,
        processing: Union[str, ProcessingFn] = "sssp",
        batch: Optional[int] = None,
    ):
        """The jitted engine callable for a partition shape — for AOT
        lowering (dry-run cells) and power users."""
        ecfg = self.config.engine_config(get_processing(processing))
        return compiled_engine(self.mesh, ecfg, n_parts, n_local, batch)

    # -- solving -------------------------------------------------------

    def solve(self, problem: Problem) -> Solution:
        with obs.span("solver.solve", spec=self.config.name) as sp:
            pg = self.partition(problem.graph)
            p = problem.processing_fn
            ecfg = self.config.engine_config(p)
            with obs.span("solver.initial_state"):
                D0, T0, L0 = initial_state(pg, p, problem.source_items())
            if ecfg.adapt_window > 0:
                sol = self._solve_adaptive(problem, pg, ecfg, D0, T0, L0)
            elif ecfg.payload != "exact":
                sol = self._solve_quantized(problem, pg, ecfg, D0, T0, L0)
            else:
                fn = compiled_engine(
                    self.mesh, ecfg, pg.n_parts, pg.n_local
                )
                # the call returns at dispatch; the fetch waits for the
                # device, so the span ends with the result on the host
                with obs.span("solver.engine"):
                    out = jax.device_get(
                        fn(*pg.on_mesh(self.mesh), D0, T0, L0)
                    )
                sol = self._pack(problem, pg, ecfg, *out)
            sp.set(supersteps=sol.metrics.supersteps,
                   converged=sol.metrics.converged)
            return sol

    def solve_batch(self, problems: Sequence[Problem]) -> list[Solution]:
        """Solve B same-shaped queries in one engine invocation: state
        arrays gain a leading batch axis over sources and the superstep
        loop is vmapped, so the graph is resident once and every
        collective amortizes over the batch.  All problems must share
        the graph and the processing function; per-query supersteps
        may report the batch maximum (converged elements idle
        harmlessly — monotonicity).

        The batch is padded to the next power of two (duplicating the
        last problem) so varying serving batch sizes bucket onto a
        handful of compiled engines instead of retracing per size; the
        padding lanes are solved and discarded (monotone no-ops for
        the caller)."""
        if not problems:
            return []
        if len(problems) == 1:
            return [self.solve(problems[0])]
        if self.config.adapt is not None:
            raise ValueError(
                "solve_batch does not support adaptive specs (/adapt): "
                "the controller would steer every lane with one "
                "shared schedule; use a static spec for batches or "
                "solve adaptive queries one at a time"
            )
        if self.config.payload != "exact":
            raise ValueError(
                "solve_batch does not support quantized payloads "
                "(/q:...): the exact repair loop re-verifies and "
                "restarts per query; use an exact payload for batches "
                "or solve quantized queries one at a time"
            )
        if self.config.trace:
            raise ValueError(
                "solve_batch does not support the flight recorder "
                "(/trace): the batched engine publishes no per-lane "
                "superstep windows; trace queries one at a time"
            )
        g0 = problems[0].graph
        p = problems[0].processing_fn
        for q in problems[1:]:
            if q.graph is not g0:
                raise ValueError("solve_batch: all problems must share a graph")
            if q.processing_fn is not p:
                raise ValueError(
                    "solve_batch: all problems must share a processing fn"
                )
        pg = self.partition(g0)
        B = len(problems)
        Bpad = batch_bucket(B)
        items = [q.source_items() for q in problems]
        items += [items[-1]] * (Bpad - B)
        ecfg = self.config.engine_config(p)
        fn = compiled_engine(
            self.mesh, ecfg, pg.n_parts, pg.n_local, batch=Bpad
        )
        D0, T0, L0 = initial_state_batch(pg, p, items)
        with obs.span("solver.solve_batch", spec=self.config.name,
                      batch=B, batch_padded=Bpad):
            D, *rest = fn(*pg.on_mesh(self.mesh), D0, T0, L0)
        D = np.asarray(D)  # (P, Bpad, n_local)
        rest = [np.asarray(r) for r in rest]  # each (Bpad,)
        return [
            self._pack(
                problems[b], pg, ecfg, D[:, b], *(r[b] for r in rest)
            )
            for b in range(B)
        ]

    def resolve(
        self,
        prev: Solution,
        new_sources=None,
        *,
        graph: Union[Graph, PartitionedGraph, None] = None,
    ) -> Solution:
        """Warm restart from a prior solution (paper §II: the kernel is
        self-stabilizing, so any state pointwise no better than the new
        fixpoint is a correct start).  ``graph`` supplies the perturbed
        graph (defaults to the previous one); ``new_sources`` adds
        initial workitems (e.g. an extra source).

        One host-side bootstrap sweep — Algorithm 1's re-verification
        step — relaxes every out-edge of the committed prior state to
        regenerate exactly the candidates the perturbation improved;
        the engine then drains only those, which is a handful of
        supersteps on a localized change instead of a full solve.

        Correct whenever the prior state dominates the new fixpoint
        (edge-weight decreases, edge/source additions).  Weight
        increases or deletions can put the fixpoint above the prior
        state, which a monotone engine cannot reach — cold-solve those.
        """
        with obs.span("solver.resolve", spec=self.config.name) as sp:
            return self._resolve(prev, new_sources, graph, sp)

    def _resolve(self, prev, new_sources, graph, sp) -> Solution:
        graph = prev.problem.graph if graph is None else graph
        p = prev.problem.processing_fn
        spec = (
            as_source_spec(new_sources)
            if new_sources is not None
            else ExplicitSources(())
        )
        problem = Problem(
            graph=graph, sources=spec, processing=prev.problem.processing
        )
        pg = self.partition(graph)
        if prev.padded.shape != (pg.n_parts, pg.n_local):
            raise ValueError(
                "resolve: previous solution was computed on a different "
                f"partition shape {prev.padded.shape} != "
                f"{(pg.n_parts, pg.n_local)}"
            )
        if prev.pg is not None and not prev.pg.same_layout(pg):
            # perm composes with warm restarts only when it is the SAME
            # perm: `padded` is in the relabeled slot space, so a
            # changed ownership map (different partitioner/seed, or a
            # perturbation that moved ebal's degree boundaries) would
            # silently seed the wrong vertices
            raise ValueError(
                "resolve: the partition layout changed between the "
                f"previous solution ({prev.pg.partitioner}) and the "
                f"new graph ({pg.partitioner}); cold-solve instead"
            )
        ecfg = self.config.engine_config(p)
        worst = np.float32(p.worst)

        # committed prior state, with the per-rank dummy slot restored
        D0 = np.concatenate(
            [prev.padded.astype(np.float32),
             np.full((pg.n_parts, 1), worst, np.float32)],
            axis=1,
        )
        with obs.span("solver.bootstrap_sweep", m=pg.m):
            T_full = _bootstrap_candidates(pg, p, prev.padded)
        for v, s, _ in problem.source_items():
            pid = int(pg.padded_id(int(v)))  # owner map: original -> slot
            T_full[pid] = p.reduce(np.float32(T_full[pid]), np.float32(s))
        T0 = np.concatenate(
            [T_full.reshape(pg.n_parts, pg.n_local),
             np.full((pg.n_parts, 1), worst, np.float32)],
            axis=1,
        )
        # warm items restart the KLA level attribute at 0 (a fresh wave)
        L0 = np.where(
            np.asarray(p.better(T0, D0)), np.float32(0.0), np.float32(np.inf)
        ).astype(np.float32)

        if ecfg.adapt_window > 0:
            sol = self._solve_adaptive(problem, pg, ecfg, D0, T0, L0)
        elif ecfg.payload != "exact":
            sol = self._solve_quantized(problem, pg, ecfg, D0, T0, L0)
        else:
            fn = compiled_engine(self.mesh, ecfg, pg.n_parts, pg.n_local)
            out = fn(*pg.on_mesh(self.mesh), D0, T0, L0)
            sol = self._pack(problem, pg, ecfg, *out)
        # account for the bootstrap sweep: one superstep's worth of
        # full-graph relaxation done host-side
        sol.metrics.relaxations += pg.m
        sol.metrics.supersteps += 1
        if sol.trace is not None:
            # the host sweep has no engine superstep window; count it
            # so SolveTrace.reconcile still balances against metrics
            sol.trace.host_sweeps += 1
        sp.set(supersteps=sol.metrics.supersteps,
               converged=sol.metrics.converged)
        return sol

    # -- internals -----------------------------------------------------

    def _solve_adaptive(
        self, problem, pg, ecfg: EngineConfig, D0, T0, L0
    ) -> Solution:
        """Segmented solve: ``/adapt`` (the repro.tune controller runs
        the segmented engine, retuning tunables between segments; a
        fresh policy instance per solve keeps controller state from
        leaking across queries), ``/trace`` (same segment engine under
        the no-op StaticPolicy, purely to publish superstep windows —
        the flight recorder collects them into ``Solution.trace``), or
        both composed."""
        from repro.tune.controller import run_adaptive
        from repro.tune.policies import StaticPolicy, make_tune_policy

        if self.config.adapt is not None:
            policy = make_tune_policy(self.config.adapt)
        else:  # pure /trace: observe without intervening
            policy = StaticPolicy()
        recorder = (
            FlightRecorder(self.config.name) if self.config.trace else None
        )
        D, m, report = run_adaptive(
            self.mesh, ecfg, pg, policy, D0, T0, L0,
            on_window=recorder.on_window if recorder is not None else None,
        )
        if self.config.adapt is not None:
            st = self._adapt_stats
            st["solves"] += 1
            st["segments"] += report.segments
            st["retraces"] += report.retraces
            st["cap_growths"] += report.cap_growths
        padded = np.asarray(D).reshape(pg.n_parts, pg.n_local)
        return self._solution(
            problem, pg, padded, m,
            trace=recorder.finish(m) if recorder is not None else None,
        )

    def _solve_quantized(
        self, problem, pg, ecfg: EngineConfig, D0, T0, L0
    ) -> Solution:
        """Quantized-payload (``/q:...``) solve + exact repair loop.

        The quantized exchange only ever *inflates* candidate values
        (round-up codes; verify-failed codes decode to +inf), so the
        state the engine converges to is pointwise >= the exact
        fixpoint, with the initial workitems committed exactly.  One
        host-side re-verification sweep (the same
        ``_bootstrap_candidates`` that powers ``resolve``) then either
        certifies the fixpoint — no edge improves any committed value,
        which with exact initial commits pins the state to the least
        fixpoint — or seeds an exact warm restart from the improving
        candidates.  Every restart strictly lowers some committed
        value (monotone commits), so the loop terminates; final states
        are bit-identical to an exact-payload solve.
        """
        p = problem.processing_fn
        fn = compiled_engine(self.mesh, ecfg, pg.n_parts, pg.n_local)
        worst = np.float32(p.worst)
        on_dev = pg.on_mesh(self.mesh)
        (D, it, commits, relax, classes, active, fallbacks, streak,
         chunks, slots, local) = fn(*on_dev, D0, T0, L0)
        it_t, commits_t = int(it), int(commits)
        relax_t, classes_t = int(relax), int(classes)
        fallbacks_t, streak_max = int(fallbacks), int(streak)
        chunks_t, slots_t, local_t = int(chunks), int(slots), int(local)
        sweeps = verifies = 0
        while int(active) == 0:  # truncated runs skip repair (warned)
            padded = np.asarray(D).reshape(pg.n_parts, pg.n_local)
            T_full = _bootstrap_candidates(pg, p, padded)
            verifies += 1
            if not bool(np.asarray(p.better(T_full, padded.reshape(-1))).any()):
                break  # certified: the exact least fixpoint
            if sweeps >= QUANT_REPAIR_MAX_SWEEPS:
                import warnings

                warnings.warn(
                    f"quantized repair loop hit "
                    f"{QUANT_REPAIR_MAX_SWEEPS} restarts without "
                    "certifying the exact fixpoint; the returned state "
                    "may retain inflated values",
                    RuntimeWarning,
                    stacklevel=3,
                )
                break
            sweeps += 1
            obs.event("repair_sweep", sweep=sweeps)
            D0r = np.concatenate(
                [padded, np.full((pg.n_parts, 1), worst, np.float32)],
                axis=1,
            )
            T0r = np.concatenate(
                [T_full.reshape(pg.n_parts, pg.n_local),
                 np.full((pg.n_parts, 1), worst, np.float32)],
                axis=1,
            )
            L0r = np.where(
                np.asarray(p.better(T0r, D0r)),
                np.float32(0.0), np.float32(np.inf),
            ).astype(np.float32)
            (D, it, commits, relax, classes, active, fallbacks, streak,
             chunks, slots, local) = fn(*on_dev, D0r, T0r, L0r)
            it_t += int(it)
            commits_t += int(commits)
            relax_t += int(relax)
            classes_t += int(classes)
            fallbacks_t += int(fallbacks)
            streak_max = max(streak_max, int(streak))
            chunks_t += int(chunks)
            slots_t += int(slots)
            local_t += int(local)
        m = _finish_metrics(
            pg, ecfg, it_t, commits_t, relax_t, classes_t, active,
            fallbacks_t, streak_max, chunks_t, slots_t, local_t,
        )
        # each host-side re-verification sweep is one superstep's worth
        # of full-graph relaxation, moving no exchange bytes
        m.relaxations += pg.m * verifies
        m.supersteps += verifies
        m.repair_sweeps = sweeps
        padded = np.asarray(D).reshape(pg.n_parts, pg.n_local)
        return self._solution(problem, pg, padded, m)

    def _pack(
        self, problem, pg, ecfg, D, it, commits, relax, classes,
        active=None, fallbacks=0, overflow_streak=0, push_chunks=0,
        exchange_slots=0, exchange_local=0,
    ) -> Solution:
        padded = np.asarray(D).reshape(pg.n_parts, pg.n_local)
        m = _finish_metrics(
            pg, ecfg, it, commits, relax, classes, active, fallbacks,
            overflow_streak, push_chunks, exchange_slots, exchange_local,
        )
        return self._solution(problem, pg, padded, m)

    def _solution(self, problem, pg, padded, metrics, trace=None) -> Solution:
        """The Solution of a padded (P, n_local) state, mapped back to
        the original vertex ids."""
        with obs.span("solver.unpermute", n=pg.n):
            state = pg.unpermute(padded.reshape(-1))
        return Solution(
            state=state,
            metrics=metrics,
            problem=problem,
            config=self.config,
            padded=padded,
            pg=pg,
            trace=trace,
        )


# back-compat alias; the canonical helper lives in the graph layer so
# other derived-buffer memos (e.g. selfstab's transpose-ELL cache) can
# share it
_graph_fingerprint = graph_fingerprint


def _bootstrap_candidates(
    pg: PartitionedGraph, p: ProcessingFn, committed: np.ndarray
) -> np.ndarray:
    """One synchronous relaxation of every out-edge of ``committed``
    ((P, n_local)) — the self-stabilizing kernel's re-verification
    sweep, done host-side over the partitioned ELL buffers.  Returns
    the (n_pad,) candidate array to seed T with."""
    worst = np.float32(p.worst)
    # per-rank row states with the dummy slot (row_src == n_local)
    state_ext = np.concatenate(
        [committed.astype(np.float32),
         np.full((pg.n_parts, 1), worst, np.float32)],
        axis=1,
    )  # (P, n_local+1)
    src_state = np.take_along_axis(state_ext, pg.row_src, axis=1)  # (P, R)
    cand = np.asarray(
        p.edge_update(src_state[:, :, None], pg.wgt), dtype=np.float32
    )
    cand = np.broadcast_to(cand, pg.wgt.shape)
    buf = np.full(pg.n_pad + 1, worst, np.float32)  # slot n_pad: padding
    if p.reduce is jnp.minimum:
        np.minimum.at(buf, pg.col.reshape(-1), cand.reshape(-1))
    else:
        np.maximum.at(buf, pg.col.reshape(-1), cand.reshape(-1))
    return buf[: pg.n_pad]


def solve(
    problem: Problem,
    config: Union[str, SolverConfig, None] = None,
    mesh=None,
) -> Solution:
    """One-shot convenience: ``Solver(config, mesh).solve(problem)``
    (still hits the process-wide engine cache)."""
    return Solver(config, mesh=mesh).solve(problem)
