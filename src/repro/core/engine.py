"""Distributed EAGM execution engine (shard_map + lax collectives).

This is the TPU-native realization of the paper's AGM/EAGM semantics
(DESIGN.md §2).  The graph is 1D-partitioned (paper §V); pending
workitems are a *dense frontier*: per owned vertex v the device keeps

    D[v] — committed state (the paper's ``distance`` mapping), and
    T[v] — the best pending workitem state for v (min over all
           outstanding ⟨v, s⟩ workitems; min-monotonicity makes the
           dominated ones semantically inert, they only ever counted
           as the paper's wasted work).

``v`` is a pending workitem iff ``better(T[v], D[v])``.

One loop iteration = one superstep:

  1.+2. fold over the EAGM ordering hierarchy (core/eagm.py): the
     GLOBAL annotation is the AGM root (global pmin of class keys ⇒
     the current smallest equivalence class); every further
     annotation refines eligibility *within* the selection above it
     at its spatial scope — pod (pmin over intra-pod axes), device
     (local reduction only), or a TopK drain (local top-B).  One code
     path realizes every family member; less synchronization at lower
     levels, the paper's §IV knob.
  3. commit eligible workitems (atomic in the dataflow sense),
  4. relax their out-edges (ELL min-plus, fat rows pre-chunked),
  5. exchange candidates to owners: paper-faithful baseline = dense
     all-reduce-min (`pmin`); optimized = all_to_all transpose +
     local min (a min-reduce-scatter, (P-1)/P of the bytes and no
     full-|V| receive buffer) — the beyond-paper §Perf variant,
  6. fold into T, count pending via psum ⇒ termination detection
     (active-work count, paper §II).

Each step runs under a ``jax.named_scope``, so every op of the loop
body carries its phase in its HLO ``op_name`` and a profiler trace can
be split by phase: ``eligibility`` (1-3), ``compact`` (the sparse
path's row compaction), ``relax`` (4; ``relax/push`` and
``relax/dense``), ``exchange`` (5) and ``vote`` (6).  Scopes are
metadata only: the compiled program is otherwise unchanged.

Frontier-sparse path (``exchange='sparse'`` / ``'auto'``): instead of
relaxing all R rows and moving O(|V|) floats, the eligible rows are
compacted into a fixed-capacity index list (cap F, the
``frontier_cap`` knob; see core/frontier.py) and only those rows are
gathered and relaxed (push mode, :func:`push_relax`: chunks of K
rows while rows are live, so the gathers and the scatter-min follow
the live frontier, not its capacity — the Pallas realization is
kernels/relax_push); candidates are slotted into per-destination-rank
(idx, val) buffers of capacity S ≈ F·W/P and moved with ONE
``all_to_all`` — per-superstep communication scales with the frontier
capacity, not |V|.  Overflow of either capacity falls back to the
dense path *for that superstep only* (the fallback decision is made
globally uniform with a pmin so every rank takes the same collective
branch); ``'auto'`` additionally prefers the dense exchange while the
carried global pending count is large.  Both paths produce bit-
identical candidate buffers, so results match the dense engine
exactly.  The carry threads the dense-exchange superstep count out to
:class:`repro.core.metrics.WorkMetrics` (each branch moves a
statically known word count per superstep, so the facade reconstructs
exact exchange bytes host-side in Python ints), the push relax's
chunk trips, the filled slots of the sparse payloads, the own
candidates combined without them, plus the final active count for
convergence/truncation detection.  Each device's own segment of the
candidates never enters the sparse payload: it is combined on the
device, so only the P-1 remote segments are packed and unpacked (on
one device, none).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.frontier import (
    PAYLOAD_MODES,
    compact_rows,
    frontier_caps,
    payload_plane_words,
    push_chunk,
    sparse_payload,
    unpack_combine,
)
from repro.core.eagm import EAGMPolicy, Hierarchy, as_hierarchy
from repro.core.metrics import WorkMetrics
from repro.core.ordering import DeltaStepping, suggest
from repro.core.processing import ProcessingFn, SSSP
from repro.graph.partition import PartitionedGraph

INF = jnp.float32(jnp.inf)


#: valid candidate-exchange strategies:
#:   'a2a'    dense all_to_all transpose + local combine (reduce-scatter)
#:   'pmin'   dense all-reduce combine (the paper-faithful baseline)
#:   'sparse' frontier-compacted (idx, val) exchange, dense fallback on
#:            capacity overflow
#:   'auto'   'sparse' while the carried pending count is small, dense
#:            otherwise
EXCHANGE_MODES = ("a2a", "pmin", "sparse", "auto")


#: valid relaxation backends for the sparse push path:
#:   'ref'    inline jnp gather/relax/scatter (XLA fuses it fine)
#:   'pallas' / 'pallas_interpret'   kernels/relax_push — Pallas gather
#:            + relax, XLA scatter
#:   'fused'  / 'fused_interpret'    kernels/superstep_fused — gather +
#:            relax + scatter-min in ONE kernel launch (no (F, W)
#:            intermediates in HBM)
#: Kernel impls apply to min-plus (sssp) processing without levels and
#: silently keep 'ref' otherwise (the analyze 'fused-kernel-escape'
#: lint surfaces that); '*_interpret' forces the Pallas interpreter,
#: which is also auto-selected on backends without a Mosaic compiler.
RELAX_IMPLS = ("ref", "pallas", "pallas_interpret", "fused",
               "fused_interpret")


def _interpret_kernels(relax_impl: str) -> bool:
    """Pallas kernels run interpreted when explicitly requested or when
    the backend has no Mosaic compiler (CPU)."""
    return relax_impl.endswith("_interpret") or jax.default_backend() == "cpu"


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    # the EAGM ordering hierarchy; a legacy EAGMPolicy or a spec
    # string is accepted and normalized to a Hierarchy, so equality /
    # the engine cache key see one canonical form
    policy: "Hierarchy | EAGMPolicy | str"
    processing: ProcessingFn = SSSP
    exchange: str = "a2a"
    max_iters: int = 10**9
    collect_metrics: bool = True
    # max eligible virtual rows compacted per device per superstep on
    # the sparse path (None = rows/8); exchange slot capacity derives
    # from it (frontier.frontier_caps)
    frontier_cap: Optional[int] = None
    # relaxation backend for the sparse push path (see RELAX_IMPLS):
    # 'ref' (inline jnp, the default) | 'pallas'[_interpret] |
    # 'fused'[_interpret]; kernels apply to min-plus processing only,
    # others stay 'ref'
    relax_impl: str = "ref"
    # sparse-exchange payload encoding (frontier.PAYLOAD_MODES):
    # 'exact' (f32 + i32, bit-identical to dense) | 'bf16' | 'u16'
    # (u32 indices + 16-bit round-up quantized value deltas — errors
    # are strictly inflationary, self-stabilization repairs them; the
    # facade's repair loop makes final states exact).  Min-reduce
    # semirings only; dense-fallback supersteps stay exact f32.
    payload: str = "exact"
    # adaptive segment window: 0 builds the classic run-to-convergence
    # loop; W > 0 builds a *segment* engine that runs at most W
    # supersteps per jitted call, threads (active, last_key, streak)
    # through as dynamic scalars, takes a dynamic delta bucket width
    # and exchange-force override, and returns the full (D, T, L)
    # state plus a (W,) per-superstep metrics window so a host-side
    # controller (repro.tune) can retune between segments.  Being an
    # EngineConfig field puts it in the engine cache key, so adaptive
    # and static engines never collide.
    adapt_window: int = 0

    def __post_init__(self):
        object.__setattr__(self, "policy", as_hierarchy(self.policy))
        if self.exchange not in EXCHANGE_MODES:
            raise ValueError(
                f"exchange must be one of {EXCHANGE_MODES}, got "
                f"{self.exchange!r}{suggest(str(self.exchange), EXCHANGE_MODES)}"
            )
        if self.frontier_cap is not None and self.frontier_cap <= 0:
            raise ValueError(f"frontier_cap must be positive: {self.frontier_cap}")
        if self.relax_impl not in RELAX_IMPLS:
            raise ValueError(
                f"relax_impl must be one of {RELAX_IMPLS}, got "
                f"{self.relax_impl!r}{suggest(str(self.relax_impl), RELAX_IMPLS)}"
            )
        if self.adapt_window < 0:
            raise ValueError(
                f"adapt_window must be >= 0: {self.adapt_window}"
            )
        if self.payload not in PAYLOAD_MODES:
            raise ValueError(
                f"payload must be one of {PAYLOAD_MODES}, got "
                f"{self.payload!r}{suggest(str(self.payload), PAYLOAD_MODES)}"
            )
        if self.payload != "exact" and self.processing.reduce is not jnp.minimum:
            raise ValueError(
                f"quantized payload {self.payload!r} requires a min-reduce "
                f"semiring (round-up errors must be inflationary); "
                f"processing fn {self.processing.name!r} reduces with "
                f"{getattr(self.processing.reduce, '__name__', self.processing.reduce)}"
            )

    @property
    def hierarchy(self) -> Hierarchy:
        """The normalized ordering hierarchy (alias of ``policy``)."""
        return self.policy


def _flat_rank(axis_names, mesh_shape):
    r = jnp.int32(0)
    for name, size in zip(axis_names, mesh_shape):
        r = r * size + jax.lax.axis_index(name)
    return r


def _ranks_within_pod(axis_names):
    """Axis names forming the intra-pod scope (all but 'pod')."""
    return tuple(a for a in axis_names if a != "pod")


def combine_into(buf, cols, vals, is_min: bool):
    """Scatter-combine edge candidates into ``buf``: min (or max) per
    destination; the last slot swallows ELL padding."""
    cols, vals = cols.reshape(-1), vals.reshape(-1)
    return buf.at[cols].min(vals) if is_min else buf.at[cols].max(vals)


def level_into(buf, cols, cands, lvl_cands, C):
    """Scatter-min into ``buf`` the levels of the candidates that
    match the winning value ``C`` (the KLA deterministic tie-break)."""
    n_pad = C.shape[0]
    win = (
        (lvl_cands < INF)
        & (cands == C[jnp.clip(cols, 0, n_pad - 1)])
        & (cols < n_pad)
    )
    return buf.at[cols.reshape(-1)].min(
        jnp.where(win, lvl_cands, INF).reshape(-1)
    )


def push_relax(p: ProcessingFn, D, L, f_idx, f_cnt, row_src, col, wgt,
               n_pad: int, use_level: bool):
    """Push relax of a compacted frontier, sized by its live rows.

    ``f_idx`` is :func:`~repro.core.frontier.compact_rows`'s (row_cap,)
    list: the ``f_cnt`` live rows first, then the sentinel R.  It is
    walked in chunks of K = :func:`~repro.core.frontier.push_chunk`
    rows: each trip of a ``fori_loop`` with a traced bound gathers K
    rows' columns, sources and weights (sentinel rows fill to the
    dummy vertex and slot, so they annihilate) and scatter-combines
    their candidates into the loop-carried (n_pad+1,) buffer.  ceil(f_cnt/K) trips cover every
    live row, and min/max is order-free, so C is bit-identical to one
    scatter over the whole capacity.  KLA levels take a second chunked
    pass once C is final.

    Returns ``(C, CL, trips)``: (n_pad,) candidates, their levels
    (zeros without ``use_level``) and the chunks run.
    """
    R = col.shape[0]
    n_local = D.shape[0] - 1
    cap = f_idx.shape[0]
    K = push_chunk(cap)
    n_max = -(-cap // K)
    ids = jnp.pad(f_idx, (0, n_max * K - cap), constant_values=R)
    # f_cnt <= cap wherever the push relax is chosen; a vmapped cond
    # runs both branches, so bound the trips for the overflowing lanes
    # (int32 constants throughout: weak scalars in the loop fork dtypes)
    k = jnp.int32(K)
    trips = jnp.minimum((f_cnt + (k - 1)) // k, jnp.int32(n_max))
    is_min = p.reduce is jnp.minimum

    def rows(i):
        f = jax.lax.dynamic_slice(ids, (i * k,), (K,))
        colg = jnp.take(col, f, axis=0, mode="fill", fill_value=n_pad)
        srcg = jnp.take(row_src, f, mode="fill", fill_value=n_local)
        wgtg = jnp.take(wgt, f, axis=0, mode="fill", fill_value=jnp.inf)
        # every gathered row is eligible (sentinel rows point at the
        # dummy vertex, whose state is `worst`), so no masking
        cand = jnp.broadcast_to(
            p.edge_update(D[srcg][:, None], wgtg), wgtg.shape
        )
        return colg, srcg, wgtg, cand

    def relax(i, buf):
        colg, _, _, cand = rows(i)
        return combine_into(buf, colg, cand, is_min)

    buf = jnp.full((n_pad + 1,), p.worst, dtype=jnp.float32)
    C = jax.lax.fori_loop(jnp.int32(0), trips, relax, buf)[:n_pad]
    if not use_level:
        return C, jnp.zeros_like(C), trips

    def level(i, lbuf):
        colg, srcg, wgtg, cand = rows(i)
        lvl = jnp.where(wgtg < INF, (L[srcg] + 1.0)[:, None], INF)
        return level_into(lbuf, colg, cand, lvl, C)

    lbuf = jnp.full((n_pad + 1,), INF, dtype=jnp.float32)
    CL = jax.lax.fori_loop(jnp.int32(0), trips, level, lbuf)[:n_pad]
    return C, CL, trips


def build_step(
    cfg: EngineConfig,
    axis_names: tuple,
    mesh_shape: tuple,
    n_local: int,
    n_parts: int,
):
    """Build the shard_map-inner superstep body + loop."""
    p = cfg.processing
    hier = cfg.hierarchy
    use_level = hier.needs_level
    is_min = p.reduce is jnp.minimum
    worst = jnp.float32(p.worst)
    n_pad = n_parts * n_local
    all_axes = axis_names
    pod_axes = _ranks_within_pod(axis_names)
    sparse_mode = cfg.exchange in ("sparse", "auto")
    # f32 planes moved by the dense exchange (values [+ KLA levels])
    nplanes = 2 if use_level else 1

    def scatter_reduce(col, vals, size):
        """Dense scatter-combine of edge candidates into a (size+1,)
        buffer (slot `size` swallows ELL padding)."""
        buf = jnp.full((size + 1,), worst, dtype=jnp.float32)
        return combine_into(buf, col, vals, is_min)

    def reduce2(a, b):
        return p.reduce(a, b)

    def local_extreme(x):
        return jnp.min(x) if is_min else jnp.max(x)

    def pextreme(x, axes):
        return jax.lax.pmin(x, axes) if is_min else jax.lax.pmax(x, axes)

    adaptive = cfg.adapt_window > 0

    def step(row_src, col, wgt, dyn, carry):
        if adaptive:
            (D, T, L, it, active, commits, relax, classes, last_key,
             fallbacks, streak, max_streak, chunks, slots, local,
             pend_w, elig_w, rows_w, sparse_w) = carry
            delta_dyn, force_ex = dyn
        else:
            (D, T, L, it, active, commits, relax, classes, last_key,
             fallbacks, streak, max_streak, chunks, slots, local) = carry
        active_prev = active
        sp_used = jnp.int32(0)
        R, W = col.shape
        if sparse_mode:
            row_cap, slot_cap = frontier_caps(
                R, W, n_local, n_parts, cfg.frontier_cap
            )
            # 'auto' heuristic: the carried pending count (an
            # overestimate of the next eligible class) gates sparse —
            # with more than half the graph pending the frontier is
            # dense by definition; below that, try sparse and let the
            # capacity-overflow veto catch the bursty supersteps
            auto_thresh = max(1, (n_parts * n_local) // 2)

        # ---- 1+2. ordering hierarchy: fold over annotations ----------
        # Each annotation refines eligibility strictly *within* the
        # previous level's selection (the EAGM extension condition),
        # using the cheapest collective its spatial scope allows:
        # global/pod -> pmin over the scope's mesh axes, device ->
        # local reduction, drain (TopK) -> local top-B.  The first
        # annotation is the AGM root; its class key feeds the
        # distinct-classes metric.
        with jax.named_scope("eligibility"):
            pending = p.better(T, D)
            eligible = pending
            kmin = INF
            for ai, (lvl, o) in enumerate(hier.annotations):
                if adaptive and ai == 0 and isinstance(o, DeltaStepping):
                    # dynamic bucket width: the same op sequence as
                    # DeltaStepping.class_key with delta a traced scalar —
                    # bit-identical to the static engine whenever the
                    # scalar equals the spec's constant, retunable by the
                    # controller without retracing
                    raw_key = jnp.floor(T / delta_dyn)
                else:
                    raw_key = o.class_key(T, L)
                key = jnp.where(eligible, raw_key, INF)
                if lvl in ("global", "pod"):
                    axes = all_axes if lvl == "global" else pod_axes
                    m = jnp.min(key)
                    if axes:
                        m = jax.lax.pmin(m, axes)
                    eligible = eligible & (key == m)
                    if lvl == "global":
                        kmin = m
                elif getattr(o, "drain", None) is not None:  # local top-B drain
                    B = min(o.drain, n_local)
                    kth = -jax.lax.top_k(-key, B)[0][B - 1]
                    eligible = eligible & (key <= kth)
                else:  # device/chunk minimal class, collective-free
                    eligible = eligible & (key == jnp.min(key))

            # ---- 3. commit (atomic monotone state update) -------------
            D = jnp.where(eligible, T, D)

        # ---- 4. relax out-edges of eligible vertices (ELL) ------------
        no_chunks = jnp.int32(0)

        @jax.named_scope("dense")
        def relax_dense(_):
            """Pull sweep over all R virtual rows (masked); returns
            (C, CL, push chunks run = 0)."""
            if is_min:
                # §Perf(S2): semiring-implicit masking — mask at the
                # (n_local,) vertex level and let +inf padding
                # annihilate padded slots (inf + w = inf = identity of
                # min).  Avoids materializing two (R, W) mask/select
                # buffers per step.
                Dm = jnp.where(eligible, D, worst)  # (n_local+1,)
                src_val = Dm[row_src]               # (R,)
                cand = jnp.broadcast_to(
                    p.edge_update(src_val[:, None], wgt), wgt.shape
                )  # (R, W); CC's update ignores wgt -> explicit bcast.
                # Padded ELL slots always carry col == n_pad, so they
                # land in the discarded dummy scatter slot for ANY
                # semiring.
            else:
                src_on = eligible[row_src]
                src_val = jnp.where(src_on, D[row_src], worst)
                cand = p.edge_update(src_val[:, None], wgt)
                cand = jnp.where(src_on[:, None] & (wgt < INF), cand, worst)
            C = scatter_reduce(col, cand, n_pad)[:n_pad]
            if not use_level:
                return C, jnp.zeros_like(C), no_chunks
            live = eligible[row_src][:, None] & (wgt < INF)
            lvl_cand = jnp.where(live, (L[row_src] + 1.0)[:, None], INF)
            lbuf = jnp.full((n_pad + 1,), INF, dtype=jnp.float32)
            CL = level_into(lbuf, col, cand, lvl_cand, C)[:n_pad]
            return C, CL, no_chunks

        if sparse_mode:
            with jax.named_scope("compact"):
                elig_rows = eligible[row_src]
                f_idx, f_cnt, row_overflow = compact_rows(elig_rows, row_cap)

            @jax.named_scope("push")
            def relax_push(_):
                """Push mode: gather only the eligible virtual rows,
                in chunks of K while rows are live (push_relax;
                kernels/relax_push is the TPU realization of the
                gather half, kernels/superstep_fused of the whole
                gather+relax+scatter over the full capacity)."""
                kernel_ok = p.name == "sssp" and not use_level
                if cfg.relax_impl.startswith("fused") and kernel_ok:
                    from repro.kernels.superstep_fused import fused_superstep

                    C = fused_superstep(
                        D, f_idx, f_cnt, row_src, col, wgt, n_pad,
                        interpret=_interpret_kernels(cfg.relax_impl),
                    )[:n_pad]
                    return C, jnp.zeros_like(C), no_chunks
                if cfg.relax_impl.startswith("pallas") and kernel_ok:
                    from repro.kernels.relax_push import relax_push_gather

                    colg = jnp.take(
                        col, f_idx, axis=0, mode="fill", fill_value=n_pad
                    )
                    cand = relax_push_gather(
                        D, f_idx, f_cnt, row_src, col, wgt,
                        interpret=_interpret_kernels(cfg.relax_impl),
                    )
                    return scatter_reduce(colg, cand, n_pad)[:n_pad], \
                        jnp.zeros((n_pad,), jnp.float32), no_chunks
                return push_relax(p, D, L, f_idx, f_cnt, row_src, col,
                                  wgt, n_pad, use_level)

        with jax.named_scope("relax"):
            if sparse_mode:
                # local decision, collective-free branches: a device
                # whose frontier overflows F sweeps densely on its own
                C, CL, trips = jax.lax.cond(
                    row_overflow, relax_dense, relax_push, None
                )
                chunks = chunks + trips
            else:
                C, CL, _ = relax_dense(None)

        # ---- 5. exchange candidates to owner devices ------------------
        # Each exchange returns (mine, mineL): the combined (n_local,)
        # candidates for my owned vertices and their levels (zeros when
        # unused).  Words moved are NOT carried on-device: each branch
        # moves a statically known word count per superstep, so the
        # facade reconstructs exact exchange bytes in Python ints from
        # (supersteps, dense-exchange-step count) — no int32 overflow
        # on long solves (see api.solver._finish_metrics).

        def exchange_pmin(_):
            # paper-faithful dense exchange: all-reduce-combine of the
            # full |V| candidate array ("send every update to the
            # owner"); ring all-reduce moves ~2(P-1)/P of the array
            Cg = pextreme(C, all_axes)
            me = _flat_rank(axis_names, mesh_shape)
            mine = jax.lax.dynamic_slice(Cg, (me * n_local,), (n_local,))
            if use_level:
                CLw = jnp.where(C == Cg, CL, INF)  # my levels where I win
                CLg = jax.lax.pmin(CLw, all_axes)
                mineL = jax.lax.dynamic_slice(
                    CLg, (me * n_local,), (n_local,)
                )
            else:
                mineL = jnp.zeros_like(mine)
            return mine, mineL

        def exchange_a2a(_):
            # optimized: all_to_all transpose + local combine
            # (= reduce-scatter with a min/max combiner)
            C2 = C.reshape(n_parts, n_local)
            X = jax.lax.all_to_all(
                C2, all_axes, split_axis=0, concat_axis=0, tiled=True
            )
            mine = p.reduce_array(X, axis=0)
            if use_level:
                L2 = CL.reshape(n_parts, n_local)
                XL = jax.lax.all_to_all(
                    L2, all_axes, split_axis=0, concat_axis=0, tiled=True
                )
                mineL = jnp.min(jnp.where(X == mine[None, :], XL, INF), 0)
            else:
                mineL = jnp.zeros_like(mine)
            return mine, mineL

        with jax.named_scope("exchange"):
            if cfg.exchange == "pmin":
                mine, mineL = exchange_pmin(None)
            elif cfg.exchange == "a2a":
                mine, mineL = exchange_a2a(None)
            elif cfg.exchange == "auto" and payload_plane_words(
                slot_cap, use_level, cfg.payload
            ) >= nplanes * n_local:
                # static shortcut: at these capacities the sparse payload
                # can never move fewer words than the dense reduce-scatter
                # (payload words ≥ planes·n_local), so 'auto' resolves to
                # dense at trace time — no compaction, no decision collective
                mine, mineL = exchange_a2a(None)
                fallbacks = fallbacks + 1
            else:  # 'sparse' | 'auto'
                # the device's own segment of C never leaves it: only
                # the P-1 remote segments are packed, voted on for
                # overflow, sent and unpacked, and the own one seeds
                # the combine (exact, never quantized).  On one device
                # nothing is packed and mine is C.
                me = _flat_rank(axis_names, mesh_shape)
                own = jax.lax.dynamic_slice(C, (me * n_local,), (n_local,))
                own_L = (
                    jax.lax.dynamic_slice(CL, (me * n_local,), (n_local,))
                    if use_level else None
                )
                n_own = jnp.sum((own != worst).astype(jnp.int32))
                extra = [(CL, INF)] if use_level else []
                payload, ex_overflow, ex_filled = sparse_payload(
                    C, extra, n_parts, slot_cap, worst, payload=cfg.payload,
                    self_rank=me,
                )
                cap_ok = jnp.logical_not(ex_overflow)
                ok = cap_ok
                if cfg.exchange == "auto":
                    ok = ok & (active_prev <= jnp.int32(auto_thresh))
                if adaptive:
                    # controller override: 1 forces sparse (the capacity
                    # veto still applies — exactness over preference),
                    # 2 forces dense, 0 keeps the mode's own heuristic
                    ok = jnp.where(force_ex == jnp.int32(1), cap_ok, ok)
                    ok = ok & jnp.logical_not(force_ex == jnp.int32(2))
                # the all_to_all shapes differ between branches, so every
                # rank must take the same one: agree globally (pmin of the
                # local votes — a rank whose buckets overflow vetoes).
                # Votes are pinned to strong int32: a weak-typed Python
                # scalar here would thread promotion through the carry
                # (jaxpr lint rule 'weak-scalar').  Lane 1 piggybacks the
                # capacity-overflow vote for the consecutive-overflow
                # streak, so the streak costs no extra collective round.
                over_local = row_overflow | ex_overflow
                votes = jnp.stack([
                    jnp.where(ok, jnp.int32(1), jnp.int32(0)),
                    jnp.where(over_local, jnp.int32(0), jnp.int32(1)),
                ])
                gvote = jax.lax.pmin(votes, all_axes)
                use_sp = gvote[0] > jnp.int32(0)
                overflow_g = gvote[1] == jnp.int32(0)

                def exchange_sparse(_):
                    recv = jax.lax.all_to_all(
                        payload, all_axes, split_axis=0, concat_axis=0,
                        tiled=True,
                    )
                    mine, mineL = unpack_combine(
                        recv, n_local, slot_cap, is_min, worst, use_level,
                        payload=cfg.payload, self_rank=me, own=(own, own_L),
                    )
                    if mineL is None:
                        mineL = jnp.zeros_like(mine)
                    return mine, mineL

                mine, mineL = jax.lax.cond(
                    use_sp, exchange_sparse, exchange_a2a, None
                )
                fallbacks = fallbacks + jnp.where(
                    use_sp, jnp.int32(0), jnp.int32(1)
                )
                sp_used = jnp.where(use_sp, jnp.int32(1), jnp.int32(0))
                slots = slots + sp_used * ex_filled
                local = local + sp_used * n_own
                streak = jnp.where(
                    overflow_g, streak + jnp.int32(1), jnp.int32(0)
                )
                max_streak = jnp.maximum(max_streak, streak)

        # ---- 6. fold into pending state T, votes ----------------------
        with jax.named_scope("vote"):
            mine_ext = jnp.concatenate([mine, jnp.array([worst])])
            improved = p.better(mine_ext, T)
            T = jnp.where(improved, mine_ext, T)
            if use_level:
                mineL_ext = jnp.concatenate([mineL, jnp.array([INF])])
                L = jnp.where(improved, mineL_ext, L)

            if adaptive:
                # one stacked psum publishes the whole metrics window row
                # (eligible class size, eligible ELL rows, live edge
                # relaxations) in a single collective round
                live = eligible[row_src][:, None] & (wgt < INF)
                if sparse_mode:
                    erows = f_cnt
                else:
                    erows = jnp.sum(eligible[row_src].astype(jnp.int32))
                sums = jax.lax.psum(
                    jnp.stack([
                        jnp.sum(eligible.astype(jnp.int32)),
                        erows,
                        jnp.sum(live.astype(jnp.int32)),
                    ]),
                    all_axes,
                )
                commits = commits + sums[0]
                relax = relax + sums[2]
                classes = classes + (kmin != last_key).astype(jnp.int32)
            elif cfg.collect_metrics:
                live = eligible[row_src][:, None] & (wgt < INF)
                commits = commits + jax.lax.psum(
                    jnp.sum(eligible.astype(jnp.int32)), all_axes
                )
                relax = relax + jax.lax.psum(
                    jnp.sum(live.astype(jnp.int32)), all_axes
                )
                classes = classes + (kmin != last_key).astype(jnp.int32)

            # termination detection: global count of pending workitems
            # (paper §II "active work"); kept in the carry so the while
            # predicate stays collective-free.
            pending_new = p.better(T, D)
            active = jax.lax.psum(
                jnp.sum(pending_new.astype(jnp.int32)), all_axes
            )

            if adaptive:
                pend_w = pend_w.at[it].set(active)
                elig_w = elig_w.at[it].set(sums[0])
                rows_w = rows_w.at[it].set(sums[1])
                sparse_w = sparse_w.at[it].set(sp_used)
                return (D, T, L, it + 1, active, commits, relax, classes,
                        kmin, fallbacks, streak, max_streak, chunks, slots,
                        local, pend_w, elig_w, rows_w, sparse_w)
            return (D, T, L, it + 1, active, commits, relax, classes, kmin,
                    fallbacks, streak, max_streak, chunks, slots, local)

    def cond(carry):
        it, active = carry[3], carry[4]
        return (active > 0) & (it < cfg.max_iters)

    def loop(row_src, col, wgt, D, T, L):
        carry = (
            D, T, L,
            jnp.int32(0), jnp.int32(1),
            jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.float32(jnp.nan),
            jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.int32(0), jnp.int32(0),
        )
        body = functools.partial(step, row_src, col, wgt, None)
        carry = jax.lax.while_loop(cond, lambda c: body(c), carry)
        (D, T, L, it, active, commits, relax, classes, _,
         fallbacks, _streak, max_streak, chunks, slots, local) = carry
        # `active` == 0 iff the loop converged (vs. truncation at
        # max_iters); `fallbacks` = supersteps on which a
        # sparse-capable mode used the dense exchange (capacity
        # overflow, the auto pending heuristic, or the static
        # can't-pay shortcut); `max_streak` = longest run of
        # consecutive capacity-overflow supersteps (0 in dense modes);
        # `chunks` = push-relax chunk trips, `slots` = filled slots of
        # the sparse payloads sent and `local` = own candidates combined
        # without the payload, each device counting its own and summed
        # here once.
        return (D[:n_local], it, commits, relax, classes, active,
                fallbacks, max_streak, jax.lax.psum(chunks, all_axes),
                jax.lax.psum(slots, all_axes),
                jax.lax.psum(local, all_axes))

    def segment(row_src, col, wgt, D, T, L,
                active0, last_key0, streak0, limit, delta_dyn, force_ex):
        """One adaptive segment: at most ``limit`` (≤ adapt_window)
        supersteps with the given dynamic tunables, returning full
        (D, T, L) for continuation plus segment-local counters and the
        per-superstep metrics window."""
        zw = jnp.zeros((cfg.adapt_window,), jnp.int32)
        carry = (
            D, T, L,
            jnp.int32(0), active0,
            jnp.int32(0), jnp.int32(0), jnp.int32(0),
            last_key0,
            jnp.int32(0), streak0, jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.int32(0), zw, zw, zw, zw,
        )

        def seg_cond(c):
            return (c[4] > 0) & (c[3] < limit)

        body = functools.partial(
            step, row_src, col, wgt, (delta_dyn, force_ex)
        )
        carry = jax.lax.while_loop(seg_cond, lambda c: body(c), carry)
        (D, T, L, it, active, commits, relax, classes, last_key,
         fallbacks, streak, max_streak, chunks, slots, local,
         pend_w, elig_w, rows_w, sparse_w) = carry
        return (D, T, L, it, commits, relax, classes, active, fallbacks,
                last_key, streak, max_streak,
                pend_w, elig_w, rows_w, sparse_w,
                jax.lax.psum(chunks, all_axes), jax.lax.psum(slots, all_axes),
                jax.lax.psum(local, all_axes))

    return segment if adaptive else loop


def make_engine(
    pg_shape: dict,
    mesh: Mesh,
    cfg: EngineConfig,
    *,
    batch: Optional[int] = None,
    trace_hook: Optional[callable] = None,
):
    """Return a jitted distributed solver for graphs with the given
    partition shape.  ``pg_shape`` = dict(n_parts, n_local, rows, width).

    ``batch=B`` builds the batched-sources engine: state arrays carry a
    batch axis — (P, B, n_local+1) in, (P, B, n_local) out — and the
    superstep loop is vmapped over it inside ``shard_map``, so B
    queries share one graph residency and one collective schedule.
    Monotonicity makes the shared loop safe: a converged batch element
    has no pending workitems, so extra supersteps are no-ops on it.

    ``trace_hook`` is called once per jit trace (not per call) — the
    facade's compile-once tests count traces through it.
    """
    axis_names = tuple(mesh.axis_names)
    mesh_shape = tuple(mesh.devices.shape)
    n_parts = pg_shape["n_parts"]
    n_local = pg_shape["n_local"]
    assert n_parts == int(np.prod(mesh_shape)), (
        f"partition parts {n_parts} != mesh devices {np.prod(mesh_shape)}"
    )

    loop = build_step(cfg, axis_names, mesh_shape, n_local, n_parts)
    shard = P(axis_names)  # leading axis split over the whole mesh

    if cfg.adapt_window > 0:
        if batch is not None:
            raise ValueError(
                "adaptive segment engines (adapt_window > 0) do not "
                "support batched sources; solve one query at a time "
                "or use a static spec for solve_batch"
            )

        def local_seg(row_src, col, wgt, D, T, L,
                      active0, last_key0, streak0, limit, delta, force):
            out = loop(row_src[0], col[0], wgt[0], D[0], T[0], L[0],
                       active0, last_key0, streak0, limit, delta, force)
            return (out[0][None], out[1][None], out[2][None]) + out[3:]

        sharded_seg = jax.shard_map(
            local_seg,
            mesh=mesh,
            in_specs=(shard,) * 6 + (P(),) * 6,
            out_specs=(shard,) * 3 + (P(),) * 16,
            # the superstep body mixes per-device and replicated values in
            # while/cond carries and calls pallas_call, neither of which
            # the varying-manual-axes checker types
            check_vma=False,
        )

        @jax.jit
        def solve_segment(row_src, col, wgt, D0, T0, L0,
                          active0, last_key0, streak0, limit, delta,
                          force):
            if trace_hook is not None:
                trace_hook()
            return sharded_seg(row_src, col, wgt, D0, T0, L0,
                               active0, last_key0, streak0, limit,
                               delta, force)

        return solve_segment

    if batch is None:
        def local(row_src, col, wgt, D, T, L):
            # shard_map hands each device a leading axis of size 1
            out = loop(row_src[0], col[0], wgt[0], D[0], T[0], L[0])
            return (out[0][None],) + out[1:]
    else:
        vloop = jax.vmap(loop, in_axes=(None, None, None, 0, 0, 0))

        def local(row_src, col, wgt, D, T, L):
            # D/T/L local slices are (1, B, n_local+1)
            out = vloop(row_src[0], col[0], wgt[0], D[0], T[0], L[0])
            return (out[0][None],) + out[1:]

    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(shard, shard, shard, shard, shard, shard),
        out_specs=(shard,) + (P(),) * 10,
        check_vma=False,  # as for the segment engine
    )

    @jax.jit
    def solve(row_src, col, wgt, D0, T0, L0):
        if trace_hook is not None:
            trace_hook()
        return sharded(row_src, col, wgt, D0, T0, L0)

    return solve


def initial_state(
    pg: PartitionedGraph, processing: ProcessingFn, sources: list[tuple]
):
    """Dense initial state from the initial workitem set S.

    ``sources`` — [(vertex, state, level)] in *original* vertex ids;
    the partition's owner map (``pg.owner_slot``, the relabeling
    permutation) places each on its owning rank.  D = worst
    everywhere, T[v] = the `processing.reduce`-combine of all initial
    workitems targeting v (duplicates keep the best state, not the
    last written one — matters for SSWP's max-reduce and multi-source
    sets with repeats); ties keep the smallest level.  Shapes
    (P, n_local+1); the trailing slot per device is the dummy target
    of padded virtual rows and stays at `worst` forever.
    """
    P_, nl = pg.n_parts, pg.n_local
    worst = np.float32(processing.worst)
    D = np.full((P_, nl + 1), worst, dtype=np.float32)
    T = np.full((P_, nl + 1), worst, dtype=np.float32)
    L = np.full((P_, nl + 1), np.inf, dtype=np.float32)
    for (v, s, lvl) in sources:
        i, j = pg.owner_slot(int(v))
        i, j = int(i), int(j)
        s, lvl = np.float32(s), np.float32(lvl)
        if bool(processing.better(s, T[i, j])):
            T[i, j] = s
            L[i, j] = lvl
        elif s == T[i, j]:
            L[i, j] = min(L[i, j], lvl)
    return D, T, L


def initial_state_batch(
    pg: PartitionedGraph,
    processing: ProcessingFn,
    sources_batch: list[list[tuple]],
):
    """Stack per-query initial states along a batch axis: (P, B,
    n_local+1) arrays for the ``batch=B`` engine."""
    per = [initial_state(pg, processing, s) for s in sources_batch]
    D = np.stack([d for d, _, _ in per], axis=1)
    T = np.stack([t for _, t, _ in per], axis=1)
    L = np.stack([l for _, _, l in per], axis=1)
    return D, T, L


def run_distributed(
    pg: PartitionedGraph,
    mesh: Mesh,
    cfg: EngineConfig,
    sources: list[tuple],
) -> tuple[np.ndarray, WorkMetrics]:
    """Deprecated: use :class:`repro.api.Solver` (compile-once cache,
    batched sources, warm restarts).  This shim keeps the old signature
    working; it routes through the facade's shared engine cache, so
    repeated calls on the same shapes no longer re-trace.
    """
    import warnings

    warnings.warn(
        "run_distributed is deprecated; use repro.api.Solver "
        "(see README 'Migrating from run_distributed')",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.api.solver import solve_with_engine_config

    return solve_with_engine_config(pg, mesh, cfg, sources)


def sssp_sources(source: int) -> list[tuple]:
    return [(int(source), 0.0, 0)]


def cc_sources(n: int) -> list[tuple]:
    return [(v, float(v), 0) for v in range(n)]
