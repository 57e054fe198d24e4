"""Work / synchronization metrics.

The container cannot time a Cray (or a TPU pod), so the benchmark
tables report the quantities the paper's wall-clock decomposes into:
work terms (relaxations = edges relaxed, commits = useful state
updates, workitems processed) and synchronization terms (equivalence
classes / supersteps, collective rounds), plus exchanged bytes.  A
calibrated linear cost model over these terms reproduces the *shape*
of the paper's comparisons (EXPERIMENTS.md §Paper-validation).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence


@dataclasses.dataclass
class WorkMetrics:
    classes: int = 0        # equivalence classes executed (root supersteps)
    workitems: int = 0      # workitems fed to the processing function
    commits: int = 0        # U evaluations that changed state (useful work)
    relaxations: int = 0    # edge relaxations (candidate generations)
    supersteps: int = 0     # distributed engine loop iterations
    exchange_bytes: int = 0  # bytes moved by candidate exchange collectives
    collective_rounds: int = 0
    converged: bool = True  # False iff the loop hit max_iters with
    #                         pending work left (state is truncated)
    sparse_fallbacks: int = 0  # supersteps on which a sparse-capable
    #   exchange mode ('sparse'/'auto') used the dense path instead —
    #   slot overflow toward a remote device, the auto pending-count
    #   heuristic, or auto's static can't-pay shortcut; 0 in plain
    #   dense modes.  A device's own candidates skip the payload and
    #   never overflow it, so on one device no fallback comes from
    #   overflow
    overflow_streak: int = 0  # longest run of *consecutive* supersteps
    #   on which sparse capacity (row, or slot toward a remote device)
    #   overflowed somewhere — the signal behind the actionable
    #   frontier_cap RuntimeWarning; on one device only the row
    #   capacity can overflow
    retraces: int = 0  # engine re-traces forced by shape-changing
    #   adaptive decisions (new frontier_cap) during this solve; 0 for
    #   static solves and for adaptive solves that only touched
    #   dynamic scalars (delta, exchange force)
    push_chunks: int = 0  # chunks of K frontier rows the sparse push
    #   relax gathered and scatter-combined, summed over supersteps and
    #   devices (core.engine.push_relax); over supersteps x
    #   ceil(row_cap / K) it is the share of a capacity-sized relax
    #   still done.  0 for dense exchange modes and the Pallas kernels
    exchange_slots: int = 0  # candidate slots filled in the sparse
    #   exchange payloads sent to remote devices, summed over supersteps
    #   and devices; over sparse supersteps x P(P-1) x slot_cap it is
    #   the live share of a payload sized by its capacity.  0 for dense
    #   exchange modes, on supersteps that fall back to the dense
    #   exchange, and on one device (nothing is sent).  Counted in
    #   int32 on the devices: it wraps past 2^31 - 1 slots a solve
    exchange_local: int = 0  # candidates a device combined for its own
    #   vertices without the payload, summed over sparse supersteps and
    #   devices (int32, as exchange_slots); on a superstep with no slot
    #   overflow, exchange_local + exchange_slots is every candidate of
    #   the sparse exchange.  0 for dense exchange modes
    repair_sweeps: int = 0  # exact warm restarts the quantized-payload
    #   repair loop needed to certify the exact fixpoint (0 for exact
    #   payloads; host re-verification sweeps are folded into
    #   relaxations/supersteps)

    def waste_ratio(self) -> float:
        """Relaxations per useful commit — the paper's redundant-work axis."""
        return self.relaxations / max(1, self.commits)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        s = (
            f"classes={self.classes} supersteps={self.supersteps} "
            f"workitems={self.workitems} commits={self.commits} "
            f"relax={self.relaxations} waste={self.waste_ratio():.2f} "
            f"xbytes={self.exchange_bytes}"
        )
        if self.push_chunks:
            s += f" push_chunks={self.push_chunks}"
        if self.exchange_slots:
            s += f" exchange_slots={self.exchange_slots}"
        if self.exchange_local:
            s += f" exchange_local={self.exchange_local}"
        # anomaly fields appear only when nonzero: the one-liner stays
        # short on clean solves but never hides the events an operator
        # needs to see (dense fallbacks, adaptive retraces, quantized
        # repairs, capacity-overflow runs)
        if self.sparse_fallbacks:
            s += f" sparse_fallbacks={self.sparse_fallbacks}"
        if self.retraces:
            s += f" retraces={self.retraces}"
        if self.repair_sweeps:
            s += f" repair_sweeps={self.repair_sweeps}"
        if self.overflow_streak:
            s += f" overflow_streak={self.overflow_streak}"
        return s + ("" if self.converged else " TRUNCATED")


@dataclasses.dataclass
class SuperstepWindow:
    """Bounded per-superstep metrics window published by an adaptive
    segment engine (``EngineConfig.adapt_window > 0``) — the
    observation a :mod:`repro.tune` controller policy maps to the next
    segment's tunables.  Lists hold one entry per superstep actually
    executed in the segment (``<= adapt_window``), all global
    (psum'd) counts; byte costs are reconstructed host-side from the
    sparse/dense choice and the segment's static capacities, so the
    window itself stays int32 on device."""

    pending: list          # global pending workitems after each superstep
    eligible: list         # global eligible-class size per superstep
    rows: list             # global eligible ELL rows per superstep
    sparse_used: list      # 1 iff the sparse exchange ran that superstep
    bytes_moved: list      # exchange bytes per superstep (host-derived)
    overflow_streak: int   # consecutive-overflow run live at segment end
    supersteps_total: int  # supersteps executed since solve start
    n: int                 # global padded vertex count (P * n_local)
    rows_per_rank: int     # ELL rows per device (frontier_cap ceiling)
    sparse_capable: bool   # exchange mode is 'sparse' or 'auto'

    def last_pending(self) -> int:
        return int(self.pending[-1]) if self.pending else 0

    def mean_eligible(self) -> float:
        if not self.eligible:
            return 0.0
        return sum(self.eligible) / len(self.eligible)


@dataclasses.dataclass
class LatencyStats:
    """Order statistics over a batch of latency samples — the serving
    tier's SLO vocabulary (p50/p99 per query, throughput over the
    window).  Percentiles use the nearest-rank method so a reported
    p99 is an actual observed sample, not an interpolation."""

    count: int = 0
    total_s: float = 0.0
    mean_s: float = 0.0
    min_s: float = 0.0
    p50_s: float = 0.0
    p90_s: float = 0.0
    p99_s: float = 0.0
    max_s: float = 0.0

    @classmethod
    def from_samples(cls, samples: Sequence[float]) -> "LatencyStats":
        xs = sorted(float(s) for s in samples)
        if not xs:
            return cls()
        def rank(pct: int) -> float:
            # nearest-rank: smallest sample with cumulative freq >= pct%
            i = (pct * len(xs) + 99) // 100  # ceil(pct·n/100), exact ints
            return xs[min(max(i - 1, 0), len(xs) - 1)]
        return cls(
            count=len(xs),
            total_s=sum(xs),
            mean_s=sum(xs) / len(xs),
            min_s=xs[0],
            p50_s=rank(50),
            p90_s=rank(90),
            p99_s=rank(99),
            max_s=xs[-1],
        )

    def merge(self, other: "LatencyStats") -> "LatencyStats":
        """Combine two windows.  count/total/mean/min/max merge
        exactly; percentiles are not mergeable from order statistics
        alone, so the merged percentile is the count-weighted mean of
        the windows' percentiles — the standard windowed-SLO
        approximation (exact when the windows are identically
        distributed)."""
        if self.count == 0:
            return dataclasses.replace(other)
        if other.count == 0:
            return dataclasses.replace(self)
        total_n = self.count + other.count
        def wmean(a: float, b: float) -> float:
            return (a * self.count + b * other.count) / total_n
        return LatencyStats(
            count=total_n,
            total_s=self.total_s + other.total_s,
            mean_s=(self.total_s + other.total_s) / total_n,
            min_s=min(self.min_s, other.min_s),
            p50_s=wmean(self.p50_s, other.p50_s),
            p90_s=wmean(self.p90_s, other.p90_s),
            p99_s=wmean(self.p99_s, other.p99_s),
            max_s=max(self.max_s, other.max_s),
        )

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return (
            f"n={self.count} p50={self.p50_s*1e3:.2f}ms "
            f"p90={self.p90_s*1e3:.2f}ms p99={self.p99_s*1e3:.2f}ms "
            f"max={self.max_s*1e3:.2f}ms"
        )


# Calibrated cost model (EXPERIMENTS.md §Paper-validation): seconds =
# a*relaxations + b*commits + c*supersteps + d*exchange_bytes.  The
# coefficients below are per-unit costs on the target (TPU v5e pod):
# an edge relaxation is a few VPU flops + an HBM access amortized over
# ELL rows; a superstep costs one small-collective latency; exchange
# bytes move at ICI bandwidth.
COST_RELAX_S = 2.0e-9       # ~0.5 Gedge/s/chip effective scatter-min
COST_SUPERSTEP_S = 15e-6    # small all-reduce latency on a pod
COST_BYTE_S = 1.0 / 45e9    # ~45 GB/s effective per-chip ICI


def model_time_s(m: WorkMetrics, n_chips: int = 1) -> float:
    """Cost-model seconds for one SSSP solve on ``n_chips`` (work terms
    divide across chips; superstep latency does not)."""
    return (
        COST_RELAX_S * m.relaxations / n_chips
        + COST_SUPERSTEP_S * m.supersteps
        + COST_BYTE_S * m.exchange_bytes / n_chips
    )
