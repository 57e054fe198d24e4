"""Frontier-sparse execution path: compaction/bucketing primitives,
sparse/auto vs dense equivalence across the paper variant grid, and
overflow-fallback correctness (multi-device semantics run in
tests/test_distributed_subprocess.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    Problem, SingleSource, Solver, SolverConfig, get_processing,
)
from repro.api.solver import engine_cache_clear
from repro.core import dijkstra_reference, frontier, paper_variant_specs
from repro.core.eagm import as_hierarchy
from repro.core.engine import push_relax
from repro.core.frontier import (
    bucket_slots,
    compact_rows,
    frontier_caps,
    push_chunk,
    scatter_plane,
    sparse_payload,
    unpack_combine,
)

rng = np.random.default_rng(11)


def close(a, b):
    return np.allclose(
        np.where(np.isinf(a), -1, a), np.where(np.isinf(b), -1, b)
    )


@pytest.fixture(scope="module")
def mesh1():
    return jax.make_mesh((1,), ("data",))


# ------------------------------------------------------------ primitives


def test_compact_rows_orders_and_flags_overflow():
    mask = jnp.array([False, True, False, True, True, False, True])
    idx, count, overflow = compact_rows(mask, 8)
    assert list(np.asarray(idx))[:4] == [1, 3, 4, 6]
    assert all(i == 7 for i in np.asarray(idx)[4:])  # sentinel = R
    assert int(count) == 4 and not bool(overflow)
    idx, count, overflow = compact_rows(mask, 2)
    assert list(np.asarray(idx)) == [1, 3]  # first-cap prefix, in order
    assert int(count) == 4 and bool(overflow)


def test_bucket_slots_and_scatter_plane():
    mask = jnp.array([[True, False, True, True], [False, False, False, True]])
    slot, overflow, filled = bucket_slots(mask, 2)
    s = np.asarray(slot)
    assert s[0, 0] == 0 and s[0, 2] == 1
    assert s[0, 1] == 2 and s[0, 3] == 2  # non-candidate + spill -> dropped
    assert s[1, 3] == 0 and bool(overflow)  # row 0 holds 3 > 2 candidates
    assert int(filled) == 3  # 2 of row 0's 3 (capped) + row 1's 1
    vals = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)
    buf = np.asarray(scatter_plane(vals, slot, 2, jnp.float32(-1.0)))
    assert buf.shape == (2, 2)
    assert buf[0, 0] == 0.0 and buf[0, 1] == 2.0
    assert buf[1, 0] == 7.0 and buf[1, 1] == -1.0


@pytest.mark.parametrize("is_min", [True, False])
def test_payload_roundtrip_matches_dense_combine(is_min):
    """pack -> (identity exchange) -> unpack == dense reduce over the
    candidate buffer, for both semirings, with room to spare."""
    P_, n_local = 4, 16
    worst = np.float32(np.inf if is_min else -np.inf)
    C = np.full(P_ * n_local, worst, np.float32)
    hot = rng.choice(P_ * n_local, 20, replace=False)
    C[hot] = rng.uniform(1, 50, 20).astype(np.float32)
    payload, overflow, filled = sparse_payload(
        jnp.asarray(C), [], P_, 8, worst
    )
    assert not bool(overflow)
    assert int(filled) == 20  # every candidate has a slot
    # single-host stand-in for all_to_all: rank r's received row p is
    # what rank p built for destination r — here every "rank" holds the
    # same C, so combining any one rank's planes against segment r
    # suffices; use segment 1.
    recv = jnp.asarray(payload)
    mine, mineL = unpack_combine(recv, n_local, 8, is_min, worst, False)
    assert mineL is None
    # oracle: per-destination-segment reduce of C (segment r of each row)
    seg = C.reshape(P_, n_local)
    # unpack_combine scatters ALL P rows of the payload into one
    # (n_local,) buffer -> equals elementwise reduce over segments
    oracle = seg.min(0) if is_min else seg.max(0)
    assert np.allclose(np.where(np.isinf(mine), -1, np.asarray(mine)),
                       np.where(np.isinf(oracle), -1, oracle))


BYPASS_P, BYPASS_NL, BYPASS_S = 4, 16, 8


def bypass_ranks(is_min, seed, hot_per_segment):
    """Every rank's (n_pad,) candidates and levels, as the engine makes
    them: levels only where a candidate is, small integer values so
    candidates of different ranks tie."""
    r = np.random.default_rng(seed)
    worst = np.float32(np.inf if is_min else -np.inf)
    Cs, CLs = [], []
    for _ in range(BYPASS_P):
        C = np.full(BYPASS_P * BYPASS_NL, worst, np.float32)
        for d in range(BYPASS_P):
            hot = d * BYPASS_NL + r.choice(BYPASS_NL, hot_per_segment,
                                           replace=False)
            C[hot] = r.integers(1, 6, hot_per_segment)
        CL = np.where(C != worst, r.integers(0, 4, C.shape), np.inf)
        Cs.append(C)
        CLs.append(CL.astype(np.float32))
    return worst, Cs, CLs


def exchange_at(me, Cs, CLs, worst, is_min, use_level, payload="exact",
                bypass=True):
    """Rank ``me``'s combine of what every rank packed for it (the
    all_to_all's row ``me`` of each rank's payload)."""
    extra = lambda CL: [(jnp.asarray(CL), np.inf)] if use_level else []
    sent = [sparse_payload(jnp.asarray(C), extra(CL), BYPASS_P, BYPASS_S,
                           worst, payload=payload,
                           self_rank=jnp.int32(r) if bypass else None)
            for r, (C, CL) in enumerate(zip(Cs, CLs))]
    recv = jnp.stack([pl[me] for pl, _, _ in sent])
    seg = slice(me * BYPASS_NL, (me + 1) * BYPASS_NL)
    own = (jnp.asarray(Cs[me][seg]),
           jnp.asarray(CLs[me][seg]) if use_level else None)
    kw = dict(self_rank=jnp.int32(me), own=own) if bypass else {}
    mine, mineL = unpack_combine(recv, BYPASS_NL, BYPASS_S, is_min, worst,
                                 use_level, payload=payload, **kw)
    return mine, mineL, sent


@pytest.mark.parametrize("use_level", [False, True])
@pytest.mark.parametrize("is_min", [True, False])
def test_own_segment_bypass_matches_full_unpack(is_min, use_level):
    """At P = 4 and every rank: leaving the own segment out of the
    payload and seeding the combine with it gives today's full
    unpack bit for bit, values and KLA levels, while the own row
    travels empty and only remote candidates take slots."""
    for seed in range(6):
        worst, Cs, CLs = bypass_ranks(is_min, seed, hot_per_segment=6)
        for me in range(BYPASS_P):
            mine_b, lvl_b, sent_b = exchange_at(
                me, Cs, CLs, worst, is_min, use_level)
            mine_f, lvl_f, sent_f = exchange_at(
                me, Cs, CLs, worst, is_min, use_level, bypass=False)
            assert np.asarray(mine_b).tobytes() == \
                np.asarray(mine_f).tobytes()
            if use_level:
                assert np.asarray(lvl_b).tobytes() == \
                    np.asarray(lvl_f).tobytes()
            else:
                assert lvl_b is None and lvl_f is None
        for r, ((pl, ov, filled), (_, ov_f, filled_f)) in enumerate(
                zip(sent_b, sent_f)):
            assert not bool(ov) and not bool(ov_f)
            own_cands = int((Cs[r].reshape(BYPASS_P, -1)[r] != worst).sum())
            assert int(filled) + own_cands == int(filled_f)
            empty = np.asarray(pl[r])
            assert (empty[:BYPASS_S] == BYPASS_NL).all()
            assert (empty[BYPASS_S:2 * BYPASS_S].view(np.float32)
                    == worst).all()


def test_own_segment_never_overflows():
    """The overflow vote covers remote destinations only: a rank whose
    own segment holds more candidates than a slot capacity still sends
    the sparse payload."""
    worst, Cs, CLs = bypass_ranks(True, 3, hot_per_segment=2)
    C = Cs[1].copy()
    C[BYPASS_NL: 2 * BYPASS_NL] = 1.0  # all of rank 1's own segment
    _, ov_b, filled_b = sparse_payload(jnp.asarray(C), [], BYPASS_P,
                                       BYPASS_S, worst,
                                       self_rank=jnp.int32(1))
    _, ov_f, _ = sparse_payload(jnp.asarray(C), [], BYPASS_P, BYPASS_S,
                                worst)
    assert bool(ov_f) and not bool(ov_b)
    assert int(filled_b) == 2 * (BYPASS_P - 1)


@pytest.mark.parametrize("payload", ["bf16", "u16"])
def test_own_segment_bypass_quantized_exact_on_own(payload):
    """Quantized payloads: the own segment is never quantized, so the
    combine is >= the exact one everywhere (round-up codes) and equal
    wherever the own candidate wins; never above the own candidate."""
    for seed in range(6):
        worst, Cs, CLs = bypass_ranks(True, seed, hot_per_segment=6)
        for C in Cs:  # spread values, so codes round
            C[C != worst] *= np.float32(1.37)
        for me in range(BYPASS_P):
            exact, _, _ = exchange_at(me, Cs, CLs, worst, True, False)
            quant, _, _ = exchange_at(me, Cs, CLs, worst, True, False,
                                      payload=payload)
            exact, quant = np.asarray(exact), np.asarray(quant)
            own = Cs[me][me * BYPASS_NL:(me + 1) * BYPASS_NL]
            assert np.all(quant >= exact)
            assert np.all(quant <= own)
            wins = own == exact
            assert wins.any()
            assert np.array_equal(quant[wins], exact[wins])


def test_frontier_caps_defaults_and_knob():
    row_cap, slot_cap = frontier_caps(1024, 16, 128, 8)
    assert row_cap == 128 and slot_cap == 64  # clamped at n_local/2
    row_cap, slot_cap = frontier_caps(1024, 16, 128, 8, frontier_cap=4)
    assert row_cap == 4 and slot_cap == 4
    # cap clamps to the row count
    row_cap, _ = frontier_caps(16, 16, 128, 8, frontier_cap=999)
    assert row_cap == 16


# ------------------------------------------------- chunked push relax

# a (row_cap,) frontier over R ELL rows of width W; row_cap > K + 1,
# so the capacity takes several chunks of K = push_chunk(row_cap) rows
PUSH_R, PUSH_W, PUSH_NL, PUSH_CAP = 5_000, 4, 300, 4_500
PUSH_K = push_chunk(PUSH_CAP)


def push_inputs(p, f_cnt, seed=7):
    """A random ELL (a quarter of the slots padding), states with some
    at ``worst``, levels, and a compacted frontier of ``f_cnt`` live
    rows padded with the sentinel R, as ``compact_rows`` leaves it."""
    r = np.random.default_rng(seed)
    R, W, nl = PUSH_R, PUSH_W, PUSH_NL
    pad = r.random((R, W)) < 0.25
    col = np.where(pad, nl, r.integers(0, nl, (R, W))).astype(np.int32)
    wgt = np.where(pad, np.inf, r.integers(1, 100, (R, W))).astype(
        np.float32)
    row_src = r.integers(0, nl, R).astype(np.int32)
    D = r.integers(0, 500, nl + 1).astype(np.float32)
    D[r.random(nl + 1) < 0.2] = p.worst
    D[nl] = p.worst
    L = r.integers(0, 9, nl + 1).astype(np.float32)
    L[nl] = np.inf
    f_idx = np.full(PUSH_CAP, R, np.int32)
    f_idx[:f_cnt] = np.sort(r.choice(R, f_cnt, replace=False))
    return D, L, f_idx, row_src, col, wgt


def push_whole_capacity(p, D, L, f_idx, row_src, col, wgt, use_level):
    """The push relax as one gather and one scatter over the whole
    (row_cap,) capacity, sentinel rows included."""
    nl = D.shape[0] - 1
    colg = jnp.take(col, f_idx, axis=0, mode="fill", fill_value=nl)
    srcg = jnp.take(row_src, f_idx, mode="fill", fill_value=nl)
    wgtg = jnp.take(wgt, f_idx, axis=0, mode="fill", fill_value=jnp.inf)
    cand = jnp.broadcast_to(p.edge_update(D[srcg][:, None], wgtg),
                            wgtg.shape)
    buf = jnp.full((nl + 1,), p.worst, jnp.float32)
    if p.reduce is jnp.minimum:
        C = buf.at[colg.reshape(-1)].min(cand.reshape(-1))[:nl]
    else:
        C = buf.at[colg.reshape(-1)].max(cand.reshape(-1))[:nl]
    if not use_level:
        return C, None
    lvl = jnp.where(wgtg < jnp.inf, (L[srcg] + 1.0)[:, None], jnp.inf)
    win = (lvl < jnp.inf) & (cand == C[jnp.clip(colg, 0, nl - 1)]) & (
        colg < nl)
    lbuf = jnp.full((nl + 1,), jnp.inf, jnp.float32)
    CL = lbuf.at[colg.reshape(-1)].min(
        jnp.where(win, lvl, jnp.inf).reshape(-1))[:nl]
    return C, CL


@pytest.mark.parametrize(
    "f_cnt", [0, 1, PUSH_K - 1, PUSH_K, PUSH_K + 1, PUSH_CAP])
@pytest.mark.parametrize("processing,spec", [
    ("sssp", "delta:5+buffer"), ("bfs", "delta:5+buffer"),
    ("cc", "delta:5+buffer"), ("sswp", "delta:5+buffer"),
    ("sssp", "kla:2+buffer"),
])
def test_chunked_push_matches_one_scatter(processing, spec, f_cnt):
    """The push relax walked in chunks of K live rows gives the
    candidate buffer (and, for KLA, the level buffer) of one scatter
    over the whole capacity, bit for bit, in ceil(f_cnt/K) trips."""
    p = get_processing(processing)
    use_level = as_hierarchy(spec).needs_level
    D, L, f_idx, row_src, col, wgt = push_inputs(p, f_cnt)
    C, CL, trips = jax.jit(
        lambda *a: push_relax(p, *a, n_pad=PUSH_NL, use_level=use_level)
    )(D, L, f_idx, jnp.int32(f_cnt), row_src, col, wgt)
    C0, CL0 = push_whole_capacity(p, D, L, f_idx, row_src, col, wgt,
                                  use_level)
    assert int(trips) == -(-f_cnt // PUSH_K)
    assert np.asarray(C).tobytes() == np.asarray(C0).tobytes()
    if use_level:
        assert np.asarray(CL).tobytes() == np.asarray(CL0).tobytes()
    else:
        assert not np.asarray(CL).any()
    if f_cnt:
        assert (np.asarray(C) != p.worst).any()


def host_push_chunks(pg, source, delta, K):
    """Replay ``delta:<delta>+buffer`` on one device with numpy and
    count the push relax's chunks: ceil(eligible rows / K) on each
    superstep whose eligible rows fit the frontier capacity.  Returns
    (supersteps, chunks)."""
    row_src, col, wgt = pg.row_src[0], pg.col[0], pg.wgt[0]
    nl, (R, W) = pg.n_local, col.shape
    row_cap, _ = frontier_caps(R, W, nl, 1)
    D = np.full(nl + 1, np.inf, np.float32)
    T = D.copy()
    T[pg.owner_slot(source)[1]] = 0.0
    steps = chunks = 0
    while (T < D).any():
        pending = T < D
        key = np.where(pending, np.floor(T / np.float32(delta)), np.inf)
        eligible = pending & (key == key.min())
        D = np.where(eligible, T, D)
        rows = eligible[row_src]
        if rows.sum() <= row_cap:
            chunks += -(-int(rows.sum()) // K)
        C = np.full(nl + 1, np.inf, np.float32)
        np.minimum.at(C, col[rows].ravel(),
                      (D[row_src[rows]][:, None] + wgt[rows]).ravel())
        C[nl] = np.inf
        T = np.minimum(T, C)
        steps += 1
    return steps, chunks


@pytest.mark.parametrize("exchange", ["sparse", "a2a", "pmin"])
def test_push_chunks_recounted_on_host(tiny_graphs, mesh1, monkeypatch,
                                       exchange):
    """``WorkMetrics.push_chunks`` is the sum over supersteps of
    ceil(eligible rows / K), as a host replay counts it (chunks of 4
    rows here, so supersteps take several); 0 without the push relax."""
    g = tiny_graphs[0]
    monkeypatch.setattr(frontier, "PUSH_CHUNK_ROWS", 4)
    engine_cache_clear()
    try:
        solver = Solver(
            SolverConfig(root="delta:5", exchange=exchange), mesh=mesh1
        )
        sol = solver.solve(Problem(g, SingleSource(0)))
    finally:
        engine_cache_clear()
    assert close(dijkstra_reference(g, 0), sol.state)
    m = sol.metrics
    if exchange != "sparse":
        assert m.push_chunks == 0 and "push_chunks" not in str(m)
        return
    pg = solver.partition(g)
    row_cap, _ = frontier_caps(pg.rows_per_rank, pg.width, pg.n_local, 1)
    steps, chunks = host_push_chunks(pg, 0, 5, push_chunk(row_cap))
    assert steps == m.supersteps
    assert m.push_chunks == chunks > m.supersteps
    assert f"push_chunks={chunks}" in str(m)


# ----------------------------------------------- dense/sparse equivalence


@pytest.mark.slow
@pytest.mark.parametrize("spec", paper_variant_specs())
def test_sparse_and_auto_match_dense_across_grid(tiny_graphs, mesh1, spec):
    """Acceptance: sparse and auto exchange produce states identical to
    the dense path for every member of the paper's variant grid."""
    g = tiny_graphs[0]
    sols = {}
    for exchange in ("a2a", "sparse", "auto"):
        solver = Solver(
            SolverConfig.from_spec(spec, exchange=exchange, chunk_size=64),
            mesh=mesh1,
        )
        sols[exchange] = solver.solve(Problem(g, SingleSource(0)))
    ref = dijkstra_reference(g, 0)
    assert close(ref, sols["a2a"].state), spec
    for exchange in ("sparse", "auto"):
        assert np.array_equal(sols["a2a"].state, sols[exchange].state), (
            spec, exchange
        )
        assert (
            sols[exchange].metrics.supersteps
            == sols["a2a"].metrics.supersteps
        ), (spec, exchange)


def test_overflow_fallback_is_correct(tiny_graphs, mesh1):
    """F smaller than the frontier: every superstep overflows into the
    dense path and the result is still exact."""
    g = tiny_graphs[1]
    ref = dijkstra_reference(g, 0)
    sol = Solver(
        SolverConfig(root="delta:5", exchange="sparse", frontier_cap=1),
        mesh=mesh1,
    ).solve(Problem(g, SingleSource(0)))
    assert close(ref, sol.state)
    dense = Solver(
        SolverConfig(root="delta:5", exchange="a2a"), mesh=mesh1
    ).solve(Problem(g, SingleSource(0)))
    assert sol.metrics.supersteps == dense.metrics.supersteps
    # one device: only the row capacity overflows (a dense relax); the
    # exchange never falls back, its candidates all stay on the device
    m = sol.metrics
    assert m.overflow_streak > 0 and m.sparse_fallbacks == 0
    assert m.exchange_slots == 0 and m.exchange_local > 0


def test_sparse_batched_sources(tiny_graphs, mesh1):
    solver = Solver("delta:5+threadq/sparse", mesh=mesh1)
    g = tiny_graphs[0]
    vs = [0, 5, 11]
    sols = solver.solve_batch([Problem(g, SingleSource(v)) for v in vs])
    for v, sol in zip(vs, sols):
        assert close(dijkstra_reference(g, v), sol.state), v


def test_sparse_other_processings(tiny_graphs, mesh1):
    """CC (min label, weightless) and SSWP (max semiring) ride the
    sparse path unchanged."""
    g = tiny_graphs[0]
    for processing in ("cc", "sswp"):
        from repro.api import EveryVertex

        src = EveryVertex() if processing == "cc" else SingleSource(0)
        dense = Solver("chaotic+buffer/a2a", mesh=mesh1).solve(
            Problem(g, src, processing=processing)
        )
        sparse = Solver("chaotic+buffer/sparse", mesh=mesh1).solve(
            Problem(g, src, processing=processing)
        )
        assert np.array_equal(dense.state, sparse.state), processing


def test_sparse_pallas_interpret_relax(tiny_graphs, mesh1):
    """The push-mode Pallas kernel (interpret mode) inside the engine
    agrees with the inline jnp path."""
    g = tiny_graphs[0]
    ref = dijkstra_reference(g, 0)
    sol = Solver(
        SolverConfig(
            root="delta:5", exchange="sparse",
            relax_impl="pallas_interpret",
        ),
        mesh=mesh1,
    ).solve(Problem(g, SingleSource(0)))
    assert close(ref, sol.state)


def test_consecutive_overflow_warns_actionably(tiny_graphs, mesh1):
    """A frontier_cap so small every superstep falls back dense must
    produce ONE RuntimeWarning naming the spec and suggesting both a
    larger cap and /adapt:rho — not a warning per superstep."""
    g = tiny_graphs[0]
    solver = Solver(
        SolverConfig(root="delta:5", exchange="sparse", frontier_cap=1),
        mesh=mesh1,
    )
    with pytest.warns(RuntimeWarning, match="frontier_cap") as rec:
        sol = solver.solve(Problem(g, SingleSource(0)))
    overflow = [w for w in rec
                if "consecutive supersteps" in str(w.message)]
    assert len(overflow) == 1
    msg = str(overflow[0].message)
    assert "delta:5+buffer/sparse" in msg  # names the spec
    assert "/adapt:rho" in msg             # names the adaptive cure
    assert sol.metrics.overflow_streak >= 3
    # a schedule whose frontier fits (dijkstra drains one class at a
    # time here) stays below the streak threshold and stays quiet
    import warnings as _w

    with _w.catch_warnings(record=True) as quiet:
        _w.simplefilter("always")
        sol2 = Solver(
            SolverConfig(root="dijkstra", exchange="sparse"), mesh=mesh1
        ).solve(Problem(g, SingleSource(0)))
    assert sol2.metrics.overflow_streak < 3
    assert not [w for w in quiet
                if "consecutive supersteps" in str(w.message)]


# Property-based sparse-vs-dense equivalence on arbitrary random
# graphs lives in tests/test_frontier_property.py (needs hypothesis).
