"""Fused superstep kernel + quantized sparse-exchange payloads.

Acceptance for the '/fused' and '/q:<dtype>' spec surface:

  * the Pallas kernel (interpret mode) is bit-identical to its
    pure-jnp oracle over randomized frontiers, including clipped fill
    rows and the ELL padding column;
  * '/fused' solves are bit-identical — state AND metrics — to the
    reference relax across the paper variant grid × {a2a, sparse};
  * quantized payloads ('/q:bf16', '/q:u16') converge to the exact
    least fixpoint bit-for-bit (the host repair loop certifies it),
    with the round-up-only encode invariant pinned at the primitive
    level;
  * the spec grammar round-trips both segments and rejects the
    compositions the engine cannot honor.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Problem, SingleSource, Solver, SolverConfig
from repro.core import dijkstra_reference, paper_variant_specs
from repro.core.eagm import as_hierarchy
from repro.core.frontier import (
    payload_plane_words,
    sparse_payload,
    unpack_combine,
)
from repro.kernels.superstep_fused import fused_superstep, fused_superstep_ref


def close(a, b):
    return np.allclose(
        np.where(np.isinf(a), -1, a), np.where(np.isinf(b), -1, b)
    )


@pytest.fixture(scope="module")
def mesh1():
    return jax.make_mesh((1,), ("data",))


# ------------------------------------------------------------- kernel


@pytest.mark.parametrize("trial", range(10))
def test_fused_kernel_matches_ref(trial):
    """Interpret-mode kernel vs the pure-jnp oracle over randomized
    frontiers: partial live counts, fill sentinels, +inf padding
    weights and the out-of-local padding column n_out."""
    r = np.random.default_rng(trial)
    R, W, n_local, n_out, F = 24, 4, 32, 48, 8
    dist = np.full(n_local + 1, np.inf, np.float32)
    hot = r.choice(n_local, 10, replace=False)
    dist[hot] = r.uniform(0.0, 9.0, 10).astype(np.float32)
    row_src = r.integers(0, n_local, R).astype(np.int32)
    col = r.integers(0, n_out + 1, (R, W)).astype(np.int32)
    wgt = np.where(
        r.random((R, W)) < 0.3, np.inf, r.uniform(0.1, 5.0, (R, W))
    ).astype(np.float32)
    k = int(r.integers(0, F + 1))
    row_idx = np.full(F, R, np.int32)  # compaction fill sentinel
    row_idx[:k] = r.choice(R, k, replace=False).astype(np.int32)
    out = fused_superstep(
        jnp.asarray(dist), jnp.asarray(row_idx), jnp.int32(k),
        jnp.asarray(row_src), jnp.asarray(col), jnp.asarray(wgt),
        n_out, interpret=True,
    )
    ref = fused_superstep_ref(
        jnp.asarray(dist), jnp.asarray(row_idx), jnp.asarray(row_src),
        jnp.asarray(col), jnp.asarray(wgt), n_out,
    )
    assert np.array_equal(np.asarray(out), np.asarray(ref)), trial


def test_fused_kernel_masks_rows_past_count():
    """Entries of row_idx past `count` point at real rows (post-clip)
    but must contribute nothing — the live-count mask, not the clip,
    is the correctness mechanism."""
    R, W, n_local, n_out = 4, 2, 4, 4
    dist = jnp.asarray([0.0, 1.0, 2.0, 3.0, np.inf], jnp.float32)
    row_src = jnp.asarray([0, 1, 2, 3], jnp.int32)
    col = jnp.asarray([[1, 2], [2, 3], [0, 4], [0, 1]], jnp.int32)
    wgt = jnp.ones((R, W), jnp.float32)
    # rows 2, 3 sit in the buffer past count=1 — only row 0 may land
    row_idx = jnp.asarray([0, 2, 3], jnp.int32)
    out = np.asarray(fused_superstep(
        dist, row_idx, jnp.int32(1), row_src, col, wgt, n_out,
        interpret=True,
    ))
    assert out[1] == 1.0 and out[2] == 1.0
    assert np.isinf(out[0]) and np.isinf(out[3])


# --------------------------------------------------- quantized payload


@pytest.mark.parametrize("payload", ["bf16", "u16"])
def test_quantized_payload_roundup_only(payload):
    """The encode invariant behind the repair loop's termination:
    decoded candidates are never below the exact candidate (errors
    are inflationary-only) and each destination segment's minimum
    survives bit-exactly."""
    r = np.random.default_rng(17)
    P_, n_local, slot_cap = 4, 16, 8
    for _ in range(50):
        C = np.full(P_ * n_local, np.inf, np.float32)
        # <= slot_cap hot candidates per destination segment: this
        # test pins the codec, not the overflow fallback
        for p in range(P_):
            k = int(r.integers(1, slot_cap + 1))
            hot = p * n_local + r.choice(n_local, k, replace=False)
            C[hot] = r.uniform(1.0, 50.0, k).astype(np.float32)
        exact, ov1, _ = sparse_payload(jnp.asarray(C), [], P_, slot_cap,
                                       np.float32(np.inf))
        quant, ov2, _ = sparse_payload(jnp.asarray(C), [], P_, slot_cap,
                                       np.float32(np.inf), payload=payload)
        assert not bool(ov1) and not bool(ov2)
        mine_e, _ = unpack_combine(
            jnp.asarray(exact), n_local, slot_cap, True,
            np.float32(np.inf), False)
        mine_q, _ = unpack_combine(
            jnp.asarray(quant), n_local, slot_cap, True,
            np.float32(np.inf), False, payload=payload)
        mine_e, mine_q = np.asarray(mine_e), np.asarray(mine_q)
        assert np.all(mine_q >= mine_e)               # round-up only
        assert mine_q.min() == mine_e.min()           # segment min exact
        assert quant.dtype == jnp.uint32


def test_payload_plane_words_quantized_fewer():
    """The words-per-destination accounting exchange_words stands on:
    both 16-bit codecs beat the exact (idx,val) planes, and the KLA
    level plane rides along un-quantized."""
    for slot_cap in (4, 8, 33):
        exact = payload_plane_words(slot_cap, False, "exact")
        bf16 = payload_plane_words(slot_cap, False, "bf16")
        u16 = payload_plane_words(slot_cap, False, "u16")
        assert exact == 2 * slot_cap
        assert bf16 == slot_cap + (slot_cap + 1) // 2 + 1
        assert u16 == slot_cap + (slot_cap + 1) // 2 + 2
        assert bf16 < exact
        # u16 carries one extra scale word, so it only wins once the
        # packed codes amortize it (any real slot_cap; ties at 4)
        assert u16 <= exact
        if slot_cap > 4:
            assert u16 < exact
        # level-bearing hierarchies add one exact f32 plane either way
        assert (payload_plane_words(slot_cap, True, "bf16")
                == bf16 + slot_cap)


@pytest.mark.parametrize("payload", ["bf16", "u16"])
def test_quantized_solve_exact_fixpoint(tiny_graphs, mesh1, payload):
    """/q:* solves certify the exact least fixpoint: final state is
    bit-identical to the exact-payload solver on every tiny graph."""
    for g in tiny_graphs:
        base = Solver(
            SolverConfig.from_spec("delta:5/sparse", chunk_size=64),
            mesh=mesh1,
        ).solve(Problem(g, SingleSource(0)))
        quant = Solver(
            SolverConfig.from_spec(
                f"delta:5/sparse/q:{payload}", chunk_size=64),
            mesh=mesh1,
        ).solve(Problem(g, SingleSource(0)))
        assert np.array_equal(base.state, quant.state)
        assert quant.metrics.converged
        assert quant.metrics.repair_sweeps >= 0
        assert base.metrics.repair_sweeps == 0


# ------------------------------------------------- engine equivalence


@pytest.mark.slow
@pytest.mark.parametrize("spec", paper_variant_specs())
def test_fused_bit_identical_across_grid(tiny_graphs, mesh1, spec):
    """Acceptance: '/fused' produces state AND metrics identical to
    the reference relax for every paper variant × {a2a, sparse}."""
    g = tiny_graphs[0]
    for exchange in ("a2a", "sparse"):
        ref = Solver(
            SolverConfig.from_spec(spec, exchange=exchange, chunk_size=64),
            mesh=mesh1,
        ).solve(Problem(g, SingleSource(0)))
        fused = Solver(
            SolverConfig.from_spec(
                spec, exchange=exchange, chunk_size=64,
                relax_impl="fused"),
            mesh=mesh1,
        ).solve(Problem(g, SingleSource(0)))
        assert np.array_equal(ref.state, fused.state), (spec, exchange)
        # where the kernel engages (the sparse path, min-plus without
        # levels) it relaxes the whole frontier capacity in one launch
        # and runs none of the reference relax's push chunks
        kernel = exchange == "sparse" and not as_hierarchy(spec).needs_level
        chunks = ref.metrics.push_chunks
        assert fused.metrics.push_chunks == (0 if kernel else chunks)
        assert dataclasses.replace(
            fused.metrics, push_chunks=chunks
        ).as_dict() == ref.metrics.as_dict(), (spec, exchange)
    assert close(dijkstra_reference(g, 0), ref.state), spec


def test_fused_quantized_compose(tiny_graphs, mesh1):
    """The two tentpole halves compose: '/fused/q:bf16' still lands on
    the exact fixpoint."""
    g = tiny_graphs[0]
    base = Solver(
        SolverConfig.from_spec("delta:5/sparse", chunk_size=64),
        mesh=mesh1,
    ).solve(Problem(g, SingleSource(0)))
    both = Solver(
        SolverConfig.from_spec("delta:5/sparse/fused/q:bf16",
                               chunk_size=64),
        mesh=mesh1,
    ).solve(Problem(g, SingleSource(0)))
    assert np.array_equal(base.state, both.state)
    assert close(dijkstra_reference(g, 0), both.state)


# -------------------------------------------------- property (hypothesis)


def test_quantized_property_random_graphs(mesh1):
    """Hypothesis sweep: on arbitrary random graphs the bf16-quantized
    solve equals the exact solve bit-for-bit (one fixed engine shape,
    compiled once — the test_frontier_property idiom)."""
    hyp = pytest.importorskip(
        "hypothesis", reason="optional dev dependency"
    )
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    from repro.graph.formats import Graph

    N, maxdeg = 24, 4

    @settings(
        max_examples=25, deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(st.data())
    def run(data):
        edges = data.draw(st.lists(
            st.tuples(
                st.integers(0, N - 1), st.integers(0, N - 1),
                st.integers(1, 31),
            ),
            min_size=1, max_size=N * maxdeg, unique_by=lambda e: e[:2],
        ))
        src = np.array([e[0] for e in edges], np.int64)
        dst = np.array([e[1] for e in edges], np.int64)
        w = np.array([e[2] for e in edges], np.float32)
        g = Graph(N, src, dst, w)
        base = Solver(
            SolverConfig.from_spec("delta:5/sparse", chunk_size=32),
            mesh=mesh1,
        ).solve(Problem(g, SingleSource(0)))
        quant = Solver(
            SolverConfig.from_spec("delta:5/sparse/q:bf16",
                                   chunk_size=32),
            mesh=mesh1,
        ).solve(Problem(g, SingleSource(0)))
        assert np.array_equal(base.state, quant.state)

    del hyp
    run()


# ------------------------------------------------------------ grammar


def test_spec_grammar_fused_and_quantized_roundtrip():
    cfg = SolverConfig.from_spec("delta:5/sparse/fused/q:bf16")
    assert cfg.relax_impl == "fused" and cfg.payload == "bf16"
    assert cfg.name == "delta:5+buffer/sparse/fused/q:bf16"
    assert SolverConfig.from_spec(cfg.name).name == cfg.name
    # bare /q defaults to bf16
    assert SolverConfig.from_spec("delta:5/sparse/q").payload == "bf16"
    # exact payload and ref impl stay silent in the name
    assert "/q" not in SolverConfig.from_spec("delta:5/sparse").name
    assert "/fused" not in SolverConfig.from_spec("delta:5/sparse").name


@pytest.mark.parametrize("bad", [
    "delta:5/sparse/fused/fused",      # duplicate segment
    "delta:5/sparse/fused:yes",        # /fused takes no argument
    "delta:5/sparse/q:",               # empty dtype
    "delta:5/sparse/q:f8",             # unknown codec
    "delta:5/sparse/q:bf16/q:u16",     # duplicate payload
])
def test_spec_grammar_rejects(bad):
    with pytest.raises(ValueError):
        SolverConfig.from_spec(bad)


def test_quantized_rejects_non_min_and_adapt_and_batch(tiny_graphs, mesh1):
    # engine level: only min-reduce processings may quantize
    from repro.api.problem import get_processing

    cfg = SolverConfig.from_spec("delta:5/sparse/q:u16")
    with pytest.raises(ValueError, match="min"):
        cfg.engine_config(get_processing("sswp"))
    # config level: /adapt and /q do not compose
    with pytest.raises(ValueError, match="adapt"):
        SolverConfig.from_spec("delta:5/sparse/adapt:rho/q:bf16")
    # solver level: batched solves bypass the repair loop -> rejected
    solver = Solver(
        SolverConfig.from_spec("delta:5/sparse/q:bf16", chunk_size=64),
        mesh=mesh1,
    )
    with pytest.raises(ValueError, match="quantized"):
        solver.solve_batch([
            Problem(tiny_graphs[0], SingleSource(0)),
            Problem(tiny_graphs[0], SingleSource(1)),
        ])


# ------------------------------------------------------ 8-device smoke

CHILD_FUSED = r"""
import numpy as np, jax
from repro.api import Problem, SingleSource, Solver, SolverConfig
from repro.core import dijkstra_reference
from repro.graph import rmat1

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
g = rmat1(9, seed=7)
ref = dijkstra_reference(g, 0)
base = Solver(SolverConfig.from_spec("delta:5/sparse", chunk_size=256),
              mesh=mesh).solve(Problem(g, SingleSource(0)))
fq = Solver(SolverConfig.from_spec("delta:5/sparse/fused/q:bf16",
                                   chunk_size=256),
            mesh=mesh).solve(Problem(g, SingleSource(0)))
assert np.allclose(np.where(np.isinf(ref), -1, ref),
                   np.where(np.isinf(base.state), -1, base.state))
assert np.array_equal(np.asarray(base.state), np.asarray(fq.state))
assert fq.metrics.exchange_bytes < base.metrics.exchange_bytes, (
    fq.metrics.exchange_bytes, base.metrics.exchange_bytes)
print("OK", base.metrics.exchange_bytes, fq.metrics.exchange_bytes)
"""


@pytest.mark.slow
def test_fused_quantized_8_devices():
    """8-rank smoke: '/fused/q:bf16' matches the exact sparse baseline
    bit-for-bit and moves strictly fewer exchange bytes."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", CHILD_FUSED], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.startswith("OK")
