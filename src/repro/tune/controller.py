"""Adaptive execution driver: segments + controller policy.

:func:`run_adaptive` is the host side of the ``EngineConfig.
adapt_window`` seam.  It repeatedly invokes the compiled *segment*
engine (at most ``adapt_window`` supersteps per call, full (D, T, L)
state threaded through device-side), turns each segment's on-device
metrics window into a :class:`repro.core.metrics.SuperstepWindow`,
and lets the policy retune the next segment's tunables:

* ``delta`` and the exchange force are *dynamic scalars* — retuning
  them reuses the compiled segment bit-for-bit (no retrace),
* ``frontier_cap`` is a static shape (compaction capacity), so a cap
  the solve has not used yet costs one engine build — counted per
  solve, surfaced via ``Solution.metrics.retraces`` and
  ``Solver.stats()``, and amortized by the process-wide engine cache
  (a repeat solve with the same decision sequence retraces nothing).

Exactness: the kernel is self-stabilizing, so retuning the ordering
mid-solve reorders the schedule but cannot move the fixpoint — the
final distances are bit-identical to any static spec of the same
semiring (machine-checked in tests/test_tune_property.py).  Byte
accounting stays exact across cap changes because each segment's
words are computed with that segment's capacities
(api.solver.exchange_words).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import numpy as np

from repro.core.engine import EngineConfig
from repro.core.frontier import frontier_caps
from repro.core.metrics import SuperstepWindow, WorkMetrics
from repro.core.ordering import DeltaStepping
from repro.obs import trace as obs
from repro.tune.policies import Decision, TunePolicy, Tunables


@dataclasses.dataclass
class AdaptReport:
    """What the controller did during one adaptive solve."""

    segments: int = 0
    retraces: int = 0      # distinct frontier_cap shapes this solve
    #                        compiled beyond the first
    cap_growths: int = 0   # cap-change decisions applied
    decisions: list = dataclasses.field(default_factory=list)
    final_delta: Optional[float] = None
    final_frontier_cap: Optional[int] = None


def run_adaptive(
    mesh,
    ecfg: EngineConfig,
    pg,
    policy: TunePolicy,
    D0,
    T0,
    L0,
    on_window: Optional[Callable[[SuperstepWindow, dict], None]] = None,
) -> tuple[np.ndarray, WorkMetrics, AdaptReport]:
    """Drive the segmented engine to convergence (or ``max_iters``)
    under ``policy``.  Returns the padded (P, n_local) committed
    state, exact WorkMetrics, and the controller's AdaptReport.

    ``on_window`` is the flight-recorder tap: when given, it is
    invoked once per segment — *including the final one, before the
    policy is consulted* — with the segment's
    :class:`~repro.core.metrics.SuperstepWindow` and a segment-info
    dict (``supersteps``, wall ``t0``/``t1`` from the tracer clock,
    the tunables in force, ``fallbacks``).  Without ``on_window`` the
    final segment's window is never materialized (it has no policy
    consumer), matching the pre-recorder behavior.
    """
    from repro.api import solver as fac  # lazy: avoids import cycles

    if ecfg.adapt_window <= 0:
        raise ValueError("run_adaptive needs an adaptive EngineConfig "
                         f"(adapt_window > 0): {ecfg.adapt_window}")
    p = ecfg.processing
    Wn = ecfg.adapt_window
    sparse_capable = ecfg.exchange in ("sparse", "auto")
    P_, nl = pg.n_parts, pg.n_local
    n = P_ * nl

    root = ecfg.hierarchy.root
    delta = float(root.delta) if isinstance(root, DeltaStepping) else None
    if sparse_capable:
        cap, _ = frontier_caps(
            pg.rows_per_rank, pg.width, nl, P_, ecfg.frontier_cap
        )
    else:
        cap = None
    force = 0

    on_dev = pg.on_mesh(mesh)
    # segments hand the state back sharded on the mesh; start it so,
    # or the first engine is traced again for the changed input type
    D, T, L = (jax.device_put(x, on_dev[0].sharding) for x in (D0, T0, L0))
    active = int(np.sum(np.asarray(p.better(T0, D0))))
    last_key = np.float32(np.nan)
    streak = 0

    it_total = 0
    commits = relax = classes = fallbacks = chunks = slots = local = 0
    words = 0
    rounds = 0
    max_streak = 0
    caps_seen = {cap}
    report = AdaptReport()

    while active > 0 and it_total < ecfg.max_iters:
        with obs.span(
            "tune.segment", segment=report.segments,
            delta=delta, frontier_cap=cap, force=force,
        ) as sp:
            if sparse_capable:
                ecfg_seg = dataclasses.replace(ecfg, frontier_cap=cap)
            else:
                ecfg_seg = ecfg
            fn = fac.compiled_engine(mesh, ecfg_seg, P_, nl)
            limit = min(Wn, ecfg.max_iters - it_total)
            t0_seg = obs.now()
            out = fn(
                *on_dev, D, T, L,
                np.int32(active), np.float32(last_key), np.int32(streak),
                np.int32(limit),
                np.float32(delta if delta is not None else np.nan),
                np.int32(force),
            )
            (D, T, L, it_a, c_a, r_a, k_a, active_a, fb_a, lk_a,
             streak_a, mstreak_a, pend_w, elig_w, rows_w, sparse_w,
             chunks_a, slots_a, local_a) = out
            it = int(it_a)
            if it == 0:
                # can't happen while active > 0 and limit >= 1, but never
                # spin on a no-progress segment
                break
            fb = int(fb_a)
            it_total += it
            commits += int(c_a)
            relax += int(r_a)
            classes += int(k_a)
            fallbacks += fb
            chunks += int(chunks_a)
            slots += int(slots_a)
            local += int(local_a)
            active = int(active_a)
            last_key = np.float32(lk_a)
            streak = int(streak_a)
            max_streak = max(max_streak, int(mstreak_a))
            words += fac.exchange_words(pg, ecfg_seg, it, fb)
            rounds += it * (3 + (1 if sparse_capable else 0))
            report.segments += 1
            t1_seg = obs.now()
            sp.set(supersteps=it, pending=active, fallbacks=fb)

            done = active == 0 or it_total >= ecfg.max_iters
            if on_window is None and done:
                break

            # host-side per-step byte costs from the sparse/dense choice
            # and THIS segment's static capacities
            sparse_steps = np.asarray(sparse_w)[:it]
            dense_b = fac.exchange_words(pg, ecfg_seg, 1, 1) * 4 * P_
            sparse_b = fac.exchange_words(pg, ecfg_seg, 1, 0) * 4 * P_
            window = SuperstepWindow(
                pending=[int(x) for x in np.asarray(pend_w)[:it]],
                eligible=[int(x) for x in np.asarray(elig_w)[:it]],
                rows=[int(x) for x in np.asarray(rows_w)[:it]],
                sparse_used=[int(x) for x in sparse_steps],
                bytes_moved=[
                    sparse_b if int(s) else dense_b for s in sparse_steps
                ],
                overflow_streak=streak,
                supersteps_total=it_total,
                n=n,
                rows_per_rank=pg.rows_per_rank,
                sparse_capable=sparse_capable,
            )
            if on_window is not None:
                on_window(window, {
                    "supersteps": it, "t0": t0_seg, "t1": t1_seg,
                    "delta": delta, "frontier_cap": cap, "force": force,
                    "fallbacks": fb,
                })
            if done:
                break
            decision = policy.decide(
                window, Tunables(delta, cap, force)
            )
            if not isinstance(decision, Decision):
                raise TypeError(
                    f"policy {type(policy).__name__} returned "
                    f"{type(decision).__name__}, expected Decision"
                )
            report.decisions.append(decision)
            sp.set(
                decision_delta=decision.delta,
                decision_frontier_cap=decision.frontier_cap,
                decision_force=decision.exchange_force,
            )
            if decision.delta is not None and delta is not None:
                d = float(decision.delta)
                if not (d > 0.0 and np.isfinite(d)):
                    raise ValueError(
                        f"policy proposed non-positive delta {d}"
                    )
                delta = d
            if decision.exchange_force is not None:
                f = int(decision.exchange_force)
                if f not in (0, 1, 2):
                    raise ValueError(
                        f"policy proposed exchange_force {f}, expected 0|1|2"
                    )
                force = f
            if decision.frontier_cap is not None and sparse_capable:
                new_cap = min(pg.rows_per_rank,
                              max(1, int(decision.frontier_cap)))
                if new_cap != cap:
                    cap = new_cap
                    report.cap_growths += 1
                    if cap not in caps_seen:
                        caps_seen.add(cap)
                        report.retraces += 1
                        fac.note_adapt_retrace()
                        obs.event("adapt_retrace", frontier_cap=cap,
                                  segment=report.segments)

    report.final_delta = delta
    report.final_frontier_cap = cap

    m = WorkMetrics(
        classes=classes,
        commits=commits,
        relaxations=relax,
        supersteps=it_total,
        workitems=commits,
        converged=(active == 0),
        sparse_fallbacks=fallbacks,
        overflow_streak=max_streak,
        retraces=report.retraces,
        push_chunks=chunks,
        exchange_slots=slots,
        exchange_local=local,
    )
    m.exchange_bytes = words * 4 * P_
    m.collective_rounds = rounds
    fac._warn_metrics(m, ecfg, pg, active)

    padded = np.asarray(D)[:, :nl]
    return padded, m, report
