"""1D vertex partitioning for the distributed AGM/EAGM engine.

Same distribution *mechanism* as the paper (§V): each rank stores the
out-edges of its owned vertices, contiguously in a padded per-rank
slot space.  The paper hardwires naive block partitioning (rank =
v // n_local); here the ownership map is a first-class, swappable
**relabeling partitioner**: a strategy computes a permutation ``perm``
of vertex ids into the padded slot space ``[0, P·n_local)`` and the
contiguous-slot engine runs unchanged on the relabeled graph.  The
engine stays completely partition-agnostic — every strategy produces
the same stacked-ELL buffer layout, only *which* vertex lands in which
(rank, slot) cell changes, and the facade un-permutes the final state
back to original vertex ids.

Strategies (``PARTITIONER_KINDS``):

* ``block`` — today's behavior, the identity relabeling (the paper's
  naive 1D distribution).
* ``shuffle:<seed>`` — pseudo-random relabeling; breaks adversarial
  id-locality (RMAT hubs cluster at low ids, so block gives one rank
  all the hubs) by spreading vertices uniformly over ranks.
* ``ebal`` — edge-balanced contiguous boundaries via a prefix sum of
  per-vertex virtual-row counts: boundaries are chosen so every rank
  gets ~the same number of ELL virtual rows, minimizing the stacked
  row count R = max over ranks (and hence the padding every rank pays
  on the dense relax path).
* ``degree`` — descending-degree striping: vertices sorted by degree
  round-robin over ranks, so hub rows spread evenly.

Because every ordering in the engine is a function of workitem
*values* (distances / levels), and min-plus relaxation is exact per
edge, the final un-permuted state is bit-identical across partitioners
— only the per-rank load balance (and, for spatially-scoped
orderings, the intermediate schedule) changes.

Two TPU-specific adaptations (unchanged from the seed):

* **Padded ELL with fat-row chunking.**  TPU programs need static
  shapes.  Rows are padded to a fixed width W; a vertex with degree
  > W is split into ceil(deg/W) *virtual rows* that share the same
  source vertex (``row_src``).  This doubles as straggler mitigation:
  no single hub vertex makes one device's relaxation row arbitrarily
  long — work per (virtual) row is bounded by W everywhere.

* **Uniform shapes across ranks.**  All per-rank buffers are padded to
  the max over ranks and stacked into leading-axis-P arrays so that
  ``shard_map`` can shard axis 0 over the device mesh.

Padding sentinels: ``col = n_pad`` (one past the last padded slot; the
scatter target array has one extra slot that is discarded) and
``weight = +inf`` (min-plus through it is a no-op).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.graph.formats import Graph, CSR, coo_to_csr, INF


def default_ell_width(avg_degree: float) -> int:
    """Power-of-two ELL width near 2x the average degree, in [4, 128]."""
    w = 1 << max(2, math.ceil(math.log2(max(1.0, 2.0 * avg_degree))))
    return int(min(128, w))


def chunk_fat_rows(
    csr: CSR, width: int, pad_col: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Split rows of ``csr`` into virtual rows of at most ``width``
    entries.  Returns (row_src, col, wgt) with shapes (R,), (R, width),
    (R, width)."""
    deg = (csr.row_ptr[1:] - csr.row_ptr[:-1]).astype(np.int64)
    chunks = np.maximum(1, -(-deg // width))  # ceil, >=1 so empty rows exist
    R = int(chunks.sum())
    row_src = np.repeat(np.arange(csr.n, dtype=np.int32), chunks)
    col = np.full((R, width), pad_col, dtype=np.int32)
    wgt = np.full((R, width), INF, dtype=np.float32)
    # For each edge, compute its (virtual_row, slot) position.
    row_start = np.zeros(csr.n + 1, dtype=np.int64)
    np.cumsum(chunks, out=row_start[1:])
    edge_row = np.repeat(np.arange(csr.n, dtype=np.int64), deg)
    edge_off = np.arange(csr.m, dtype=np.int64) - np.repeat(
        csr.row_ptr[:-1], deg
    )
    vrow = row_start[edge_row] + edge_off // width
    slot = edge_off % width
    col[vrow, slot] = csr.col_idx
    wgt[vrow, slot] = csr.weight
    return row_src, col, wgt


# ---------------------------------------------------------------------
# relabeling partitioners
# ---------------------------------------------------------------------

PARTITIONER_KINDS = ("block", "shuffle", "ebal", "degree")


def _suggest(word: str, choices) -> str:
    # late import: graph must stay importable before repro.core is
    from repro.core.ordering import suggest

    return suggest(word, choices)


def canonical_partitioner(spec: str) -> str:
    """Validate and canonicalize a partitioner spec: ``block`` |
    ``shuffle[:seed]`` | ``ebal`` | ``degree``.  Unknown kinds raise
    with a did-you-mean suggestion (EngineConfig error style);
    ``shuffle`` normalizes to ``shuffle:0`` so equal configs compare
    equal."""
    s = str(spec).strip().lower()
    if not s:
        raise ValueError(f"empty partitioner spec {spec!r}")
    kind, sep, arg = s.partition(":")
    kind = kind.strip()
    if kind not in PARTITIONER_KINDS:
        raise ValueError(
            f"unknown partitioner {spec!r}; valid kinds "
            f"{PARTITIONER_KINDS}{_suggest(kind, PARTITIONER_KINDS)}"
        )
    if kind == "shuffle":
        arg = arg.strip() or "0"
        try:
            seed = int(arg)
        except ValueError:
            raise ValueError(
                f"shuffle seed must be an integer: {spec!r}"
            ) from None
        if seed < 0:
            raise ValueError(
                f"shuffle seed must be non-negative: {spec!r}"
            )
        return f"shuffle:{seed}"
    if sep:
        raise ValueError(
            f"partitioner {kind!r} takes no argument (got {spec!r})"
        )
    return kind


@dataclasses.dataclass(frozen=True)
class Assignment:
    """A vertex→(rank, slot) ownership map, encoded as a permutation
    into the padded global slot space: vertex ``v`` lives at padded id
    ``perm[v]`` = ``rank · n_local + slot``.  Padded ids in
    ``[0, n_pad)`` not hit by ``perm`` are dummy slots (no vertex, no
    edges, state stays at ``worst``)."""

    n: int
    n_parts: int
    n_local: int
    perm: np.ndarray  # (n,) int64
    spec: str         # canonical partitioner spec

    @property
    def n_pad(self) -> int:
        return self.n_parts * self.n_local


def _positions(order: np.ndarray) -> np.ndarray:
    """Invert ``order``: position of each vertex in the sorted order.
    A contiguous even split reads this directly as the padded id
    (rank i // n_local, slot i % n_local)."""
    pos = np.empty(order.shape[0], dtype=np.int64)
    pos[order] = np.arange(order.shape[0], dtype=np.int64)
    return pos


def assign_vertices(
    g: Graph, n_parts: int, spec: str, width: int
) -> Assignment:
    """Compute the ownership permutation for ``spec`` (canonical form;
    see :func:`canonical_partitioner`)."""
    spec = canonical_partitioner(spec)
    kind, _, arg = spec.partition(":")
    n = g.n
    even_local = -(-n // n_parts)  # ceil

    if kind == "block":
        perm = np.arange(n, dtype=np.int64)
        return Assignment(n, n_parts, even_local, perm, spec)

    if kind == "shuffle":
        order = np.random.default_rng(int(arg)).permutation(n)
        return Assignment(n, n_parts, even_local, _positions(order), spec)

    deg = np.bincount(g.src, minlength=n).astype(np.int64)

    if kind == "degree":
        # descending-degree striping: sorted position i -> rank i % P,
        # slot i // P, so the heaviest rows round-robin over ranks
        pos = _positions(np.lexsort((np.arange(n), -deg)))
        perm = (pos % n_parts) * even_local + pos // n_parts
        return Assignment(n, n_parts, even_local, perm, spec)

    # ebal: contiguous boundaries balancing per-rank virtual-row counts
    # (the quantity the stacked ELL pads every rank to).  Boundaries by
    # prefix sum: rank p owns the id range whose cumulative row count
    # first reaches p/P of the total.
    rows = np.maximum(1, -(-deg // width))
    cum = np.cumsum(rows)
    total = int(cum[-1])
    targets = np.arange(1, n_parts) * (total / n_parts)
    bounds = np.searchsorted(cum, targets, side="left")
    bounds = np.concatenate([[0], bounds, [n]]).astype(np.int64)
    counts = np.diff(bounds)
    n_local = int(counts.max(initial=1))
    perm = np.empty(n, dtype=np.int64)
    for p in range(n_parts):
        lo, hi = int(bounds[p]), int(bounds[p + 1])
        perm[lo:hi] = p * n_local + np.arange(hi - lo, dtype=np.int64)
    return Assignment(n, n_parts, n_local, perm, spec)


# ---------------------------------------------------------------------
# partitioned graph
# ---------------------------------------------------------------------


@dataclasses.dataclass
class PartitionedGraph:
    """1D-partitioned graph with stacked per-rank ELL buffers.

    Shapes: ``row_src`` (P, R); ``col``/``wgt`` (P, R, W).
    Ownership: rank p owns the vertices whose padded id
    ``perm[v]`` falls in [p*n_local, (p+1)*n_local); for ``block``
    (``perm is None``, the identity) that is the classic
    [p*n_local, (p+1)*n_local) id range.
    ``col`` holds *padded global* destination ids; padding = n_pad.
    ``row_src`` holds *local* source slots (0..n_local-1); padded
    virtual rows point at local slot n_local (a dummy whose state is
    ``worst``).  This object is the single owner-mapping seam:
    :meth:`owner_slot`, :meth:`to_global` and :meth:`unpermute` are
    the only places vertex ids translate between the original and the
    padded space.
    """

    n: int            # real vertex count
    m: int            # real edge count
    n_parts: int
    n_local: int      # owned slots per rank (n_pad = P * n_local)
    width: int
    row_src: np.ndarray
    col: np.ndarray
    wgt: np.ndarray
    name: str = "pgraph"
    partitioner: str = "block"
    # relabeling permutation: original id -> padded global id.
    # None = identity (block), i.e. perm[v] == v.
    perm: Optional[np.ndarray] = None

    @property
    def n_pad(self) -> int:
        return self.n_parts * self.n_local

    @property
    def rows_per_rank(self) -> int:
        return int(self.row_src.shape[1])

    @property
    def inv_perm(self) -> np.ndarray:
        """(n_pad,) padded id -> original id, -1 on dummy slots."""
        inv = getattr(self, "_inv_perm", None)
        if inv is None:
            inv = np.full(self.n_pad, -1, dtype=np.int64)
            if self.perm is None:
                inv[: self.n] = np.arange(self.n, dtype=np.int64)
            else:
                inv[self.perm] = np.arange(self.n, dtype=np.int64)
            self._inv_perm = inv
        return inv

    def on_mesh(self, mesh) -> tuple:
        """``(row_src, col, wgt)`` as device arrays on ``mesh``, the
        rank axis split over all of its axes (the engine's ``shard_map``
        in-spec).  Placed once per mesh and memoized, so repeat solves
        copy no graph bytes to the device; the numpy buffers stay for
        host-side readers.  Nothing mutates them in place: a changed
        graph is partitioned anew, and so placed anew."""
        placed = self.__dict__.setdefault("_placed", {})
        arrs = placed.get(mesh)
        if arrs is None:
            sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names))
            arrs = tuple(
                jax.device_put(a, sharding)
                for a in (self.row_src, self.col, self.wgt)
            )
            placed[mesh] = arrs
        return arrs

    # -- the owner-mapping seam ---------------------------------------

    def padded_id(self, v):
        """Original vertex id(s) -> padded global id(s)."""
        v = np.asarray(v)
        return v if self.perm is None else self.perm[v]

    def owner_slot(self, v):
        """Original vertex id(s) -> (rank, slot)."""
        pid = self.padded_id(v)
        return pid // self.n_local, pid % self.n_local

    def owner(self, v):
        return self.owner_slot(v)[0]

    def to_global(self, rank, slot):
        """(rank, slot) -> original vertex id, -1 for dummy slots."""
        pid = np.asarray(rank) * self.n_local + np.asarray(slot)
        return self.inv_perm[pid]

    def unpermute(self, padded_state: np.ndarray) -> np.ndarray:
        """(..., n_pad) padded-space state -> (..., n) original-id
        state.  The inverse of the relabeling: for ``block`` this is
        the classic ``[:n]`` truncation."""
        padded_state = np.asarray(padded_state)
        if self.perm is None:
            return padded_state[..., : self.n]
        return padded_state[..., self.perm]

    def same_layout(self, other: "PartitionedGraph") -> bool:
        """True iff states padded under ``self`` are valid under
        ``other`` (same shape AND same vertex→slot map) — the warm-
        restart compatibility check."""
        if (self.n, self.n_parts, self.n_local) != (
            other.n, other.n_parts, other.n_local
        ):
            return False
        if (self.perm is None) != (other.perm is None):
            return False
        return self.perm is None or bool(
            np.array_equal(self.perm, other.perm)
        )

    # -- load-balance statistics --------------------------------------

    def load_stats(self) -> dict:
        """Per-rank load balance: real edges and virtual rows per rank,
        ELL occupancy, and straggler ratios (max/mean — 1.0 is perfect
        balance; the dense relax path costs every rank the padded max,
        so ``straggler_rows`` is the padding overhead of the stacked
        ELL)."""
        edges = np.sum(self.col != self.n_pad, axis=(1, 2))
        rows = np.sum(self.row_src != self.n_local, axis=1)
        def _straggler(x):
            mean = float(np.mean(x))
            return float(np.max(x)) / mean if mean > 0 else 1.0
        return dict(
            edges_per_rank=[int(e) for e in edges],
            rows_per_rank=[int(r) for r in rows],
            max_rows=self.rows_per_rank,
            ell_occupancy=float(edges.sum()) / max(1, self.col.size),
            straggler_rows=_straggler(rows),
            straggler_edges=_straggler(edges),
        )

    def describe(self, stats: Optional[dict] = None) -> str:
        st = stats if stats is not None else self.load_stats()
        return (
            f"{self.name}: n={self.n} m={self.m} P={self.n_parts} "
            f"n_local={self.n_local} rows/rank={self.rows_per_rank} "
            f"W={self.width} ell_density={st['ell_occupancy']:.3f} "
            f"partition={self.partitioner} "
            f"straggler={st['straggler_rows']:.2f}"
        )


def partition_graph(
    g: Graph,
    n_parts: int,
    width: Optional[int] = None,
    partitioner: str = "block",
    name: Optional[str] = None,
) -> PartitionedGraph:
    """Partition ``g`` over ``n_parts`` ranks under a relabeling
    strategy (see module docstring).  The returned buffers are in the
    padded relabeled space; the :class:`PartitionedGraph` carries the
    permutation for translating back."""
    spec = canonical_partitioner(partitioner)
    if width is None:
        width = default_ell_width(g.m / max(1, g.n))
    asn = assign_vertices(g, n_parts, spec, width)
    n_local, n_pad = asn.n_local, asn.n_pad

    # Relabeled graph over the padded id space: dummy slots are real
    # (degree-0) vertices here, so per-rank CSR slicing is uniform.
    perm32 = asn.perm.astype(np.int32)
    g2 = Graph(
        n_pad, perm32[g.src], perm32[g.dst], g.weight, name=g.name
    )
    csr_all = coo_to_csr(g2)
    # real vertices occupy a contiguous slot prefix [0, counts[p]) on
    # every rank (all strategies assign positionally); dummy tail slots
    # get no virtual rows at all — they have no edges and a row each
    # would defeat ebal's row balancing.
    counts = np.bincount(
        asn.perm // n_local, minlength=n_parts
    ).astype(np.int64)

    per_rank = []
    for p in range(n_parts):
        lo, hi = p * n_local, p * n_local + int(counts[p])
        row_ptr = csr_all.row_ptr[lo : hi + 1] - csr_all.row_ptr[lo]
        sl = slice(csr_all.row_ptr[lo], csr_all.row_ptr[hi])
        local = CSR(
            hi - lo, row_ptr, csr_all.col_idx[sl], csr_all.weight[sl]
        )
        per_rank.append(chunk_fat_rows(local, width, pad_col=n_pad))

    R = max(rs.shape[0] for rs, _, _ in per_rank)
    P = n_parts
    row_src = np.full((P, R), n_local, dtype=np.int32)  # pad -> dummy slot
    col = np.full((P, R, width), n_pad, dtype=np.int32)
    wgt = np.full((P, R, width), INF, dtype=np.float32)
    for p, (rs, c, w) in enumerate(per_rank):
        row_src[p, : rs.shape[0]] = rs
        col[p, : c.shape[0]] = c
        wgt[p, : w.shape[0]] = w

    return PartitionedGraph(
        n=g.n, m=g.m, n_parts=P, n_local=n_local, width=width,
        row_src=row_src, col=col, wgt=wgt, name=name or g.name,
        partitioner=spec, perm=None if spec == "block" else asn.perm,
    )


def partition_1d(
    g: Graph, n_parts: int, width: int | None = None, name: str | None = None
) -> PartitionedGraph:
    """Block 1D partitioning (the paper's §V distribution) — kept as
    the stable name for the identity-relabeling strategy."""
    return partition_graph(g, n_parts, width=width, partitioner="block",
                           name=name)
