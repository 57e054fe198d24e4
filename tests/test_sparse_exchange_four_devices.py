"""The sparse exchange between four devices: exact, bit for bit, when
its payload really crosses devices, whatever the transport does to
floats.

A payload that carried local indices as the bits of f32 words sent
each index below 2^23 as a denormal; a transport that flushes
denormals to zero (as float paths on an accelerator may) turned every
such index into vertex 0, and solves stopped after a few supersteps
with wrong answers.  The payload is u32 words now.  These tests run
one child process with four CPU devices (JAX fixes the device count
when it starts) and check what it reports.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
#: rmat1 scale 10 over four devices: a solve of about 30 supersteps of
#: which a few overflow the slot capacity toward another device and
#: fall back to the dense exchange, so both branches run in one solve
SCALE, GRAPH_SEED, SOURCES = 10, 1, (0, 17, 300)
DELTA = 5
SPARSE, A2A = "delta:5+buffer/sparse", "delta:5+buffer/a2a"


def flush_denormals_all_to_all(all_to_all):
    """``all_to_all`` over a transport that flushes f32 denormals to
    zero (keeping the sign): float words below the smallest normal
    arrive as zero, every other word as sent."""
    import jax.numpy as jnp

    def patched(x, *args, **kwargs):
        y = all_to_all(x, *args, **kwargs)
        if y.dtype != jnp.float32:
            return y
        tiny = jnp.finfo(jnp.float32).tiny
        return jnp.where(jnp.abs(y) < tiny, jnp.copysign(0.0, y), y)

    return patched


def replay(pg, source, delta, slot_cap):
    """Replay ``delta:<delta>+buffer`` with the sparse exchange over the
    partition's ranks in numpy.  A rank's candidates for its own
    vertices never enter the payload: a superstep uses the sparse
    payload when no rank holds more than ``slot_cap`` candidates for
    any other rank; it then fills one slot per remote candidate and
    combines the own ones on the rank.  Returns (supersteps, dense
    fallbacks, filled slots, own candidates combined, distances by
    padded id)."""
    P_, nl = pg.n_parts, pg.n_local
    n_pad = P_ * nl
    D = np.full((P_, nl + 1), np.inf, np.float32)
    T = D.copy()
    r0, j0 = pg.owner_slot(source)
    T[int(r0), int(j0)] = 0.0
    steps = fallbacks = slots = local = 0
    remote = ~np.eye(P_, dtype=bool)
    while (T < D).any():
        pending = T < D
        key = np.where(pending, np.floor(T / np.float32(delta)), np.inf)
        eligible = pending & (key == key.min())
        D = np.where(eligible, T, D)
        C = np.full((P_, n_pad + 1), np.inf, np.float32)
        for r in range(P_):
            rows = eligible[r][pg.row_src[r]]
            np.minimum.at(
                C[r], pg.col[r][rows].ravel(),
                (D[r][pg.row_src[r][rows]][:, None]
                 + pg.wgt[r][rows]).ravel())
        per_dest = (C[:, :n_pad] != np.inf).reshape(P_, P_, nl).sum(2)
        if per_dest[remote].max() > slot_cap:
            fallbacks += 1
        else:
            slots += int(per_dest[remote].sum())
            local += int(np.trace(per_dest))
        mine = C[:, :n_pad].reshape(P_, P_, nl).min(0)
        T[:, :nl] = np.minimum(T[:, :nl], mine)
        steps += 1
    return steps, fallbacks, slots, local, D[:, :nl].reshape(-1)


@pytest.fixture(scope="module")
def four_devices():
    """What the child saw, as JSON: per source and spec, the solve's
    metrics and whether its distances equal the host Dijkstra's, under
    a plain and a denormal-flushing transport, and the host replay."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.splitlines()[-1])


def test_sparse_exact_over_a_transport_that_flushes_denormals(four_devices):
    """The fault as it showed on the chip: with denormals flushed in
    transit the sparse exchange must still give Dijkstra's distances
    and the supersteps of the dense exchange, which carries only
    normal floats and infinities."""
    for src, runs in four_devices["flush"].items():
        assert runs[A2A]["exact"], src
        assert runs[SPARSE]["exact"], src
        assert runs[SPARSE]["supersteps"] == runs[A2A]["supersteps"], src


def test_sparse_matches_a2a_and_dijkstra_with_both_branches(four_devices):
    """``delta:5+buffer/sparse`` over four devices equals the host
    Dijkstra and the ``a2a`` spec bit for bit, in solves where some
    supersteps overflow the slot capacity and fall back to the dense
    exchange and the others send the sparse payload."""
    for src, runs in four_devices["plain"].items():
        sp, dense = runs[SPARSE], runs[A2A]
        assert sp["exact"] and dense["exact"], src
        assert sp["bits"] == dense["bits"], src
        assert sp["supersteps"] == dense["supersteps"], src
        assert 0 < sp["sparse_fallbacks"] < sp["supersteps"], src
        assert dense.get("exchange_slots") == 0, src


def test_exchange_slots_recounted_on_host(four_devices):
    """``WorkMetrics.exchange_slots`` is the number of candidates sent
    in sparse payloads, summed over supersteps and devices, as a numpy
    replay of the four ranks counts it; the supersteps, the fallbacks
    and the distances agree too."""
    for src, runs in four_devices["plain"].items():
        sp, host = runs[SPARSE], four_devices["replay"][src]
        assert sp["supersteps"] == host["supersteps"], src
        assert sp["sparse_fallbacks"] == host["fallbacks"], src
        assert sp.get("exchange_slots") == host["slots"] > 0, src
        assert f"exchange_slots={host['slots']}" in sp["str"], src
        assert host["exact"], src


def test_exchange_local_recounted_on_host(four_devices):
    """``WorkMetrics.exchange_local`` is the number of candidates each
    rank combined for its own vertices without the payload, summed over
    sparse supersteps and ranks, as the replay counts them; with the
    slots it makes every candidate of the sparse supersteps."""
    for src, runs in four_devices["plain"].items():
        sp, host = runs[SPARSE], four_devices["replay"][src]
        assert sp.get("exchange_local") == host["local"] > 0, src
        assert f"exchange_local={host['local']}" in sp["str"], src
        assert sp["exchange_local"] + sp["exchange_slots"] == \
            host["local"] + host["slots"], src
        assert runs[A2A].get("exchange_local") == 0, src


def child() -> None:
    import jax

    from repro.api import Problem, SingleSource, Solver
    from repro.api.solver import engine_cache_clear
    from repro.core import dijkstra_reference
    from repro.core.frontier import frontier_caps
    from repro.graph import rmat1

    assert jax.device_count() == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("data",))
    g = rmat1(SCALE, seed=GRAPH_SEED)
    ref = {s: dijkstra_reference(g, s) for s in SOURCES}

    def same(a, b):
        return bool(np.array_equal(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64)))

    def solve_all():
        out = {}
        for s in SOURCES:
            out[s] = {}
            for spec in (SPARSE, A2A):
                sol = Solver(spec, mesh=mesh).solve(
                    Problem(g, SingleSource(s)))
                out[s][spec] = dict(
                    sol.metrics.as_dict(), str=str(sol.metrics),
                    exact=same(sol.state, ref[s]),
                    bits=np.asarray(sol.state, np.float32).tobytes().hex())
        return out

    result = {"plain": solve_all()}
    plain_all_to_all = jax.lax.all_to_all
    jax.lax.all_to_all = flush_denormals_all_to_all(plain_all_to_all)
    engine_cache_clear()
    try:
        result["flush"] = solve_all()
    finally:
        jax.lax.all_to_all = plain_all_to_all
        engine_cache_clear()

    pg = Solver(SPARSE, mesh=mesh).partition(g)
    _, slot_cap = frontier_caps(pg.rows_per_rank, pg.width, pg.n_local,
                                pg.n_parts)
    result["replay"] = {}
    for s in SOURCES:
        steps, fallbacks, slots, local, d = replay(pg, s, DELTA, slot_cap)
        result["replay"][s] = dict(
            supersteps=steps, fallbacks=fallbacks, slots=slots,
            local=local, exact=same(pg.unpermute(d), ref[s]))
    print(json.dumps(result))


if __name__ == "__main__":
    child()
