"""The engine compiled for a described TPU v5e (no chip attached).

The TPU compiler refuses what the CPU backend accepts: tiling it cannot
lay out, programs that do not fit the device's memory.  These tests
compile ``make_engine``'s default (``ref``) program at real graph
shapes for one v5e chip and for a 2x2 mesh of four, and hold each to
the chip's 16 GiB of HBM.  Nothing runs; results are checked elsewhere.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, so a worker that loads it at
collection would stop the others from collecting the same tests.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.engine import EngineConfig, make_engine
from repro.core.frontier import frontier_caps

V5E_HBM_BYTES = 16 * 2**30

# rmat1(scale=20, seed=0) under partition_graph(g, 1): its stacked ELL
# row count at the default width (graph500 Kronecker, edge factor 16)
SCALE20 = dict(n_local=1 << 20, rows=1_416_878, width=64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler, or no such topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


def compile_engine(devices, exchange, n_local, rows, width, batch=None):
    """AOT-compile the engine over a 1-D mesh of ``devices`` for one
    rank's shapes; return the compiled program."""
    n_parts = len(devices)
    mesh = Mesh(np.array(devices), ("data",))
    ecfg = EngineConfig("delta:5+buffer", exchange=exchange)
    fn = make_engine(dict(n_parts=n_parts, n_local=n_local), mesh, ecfg,
                     batch=batch)
    shard = NamedSharding(mesh, PartitionSpec(("data",)))
    state = (n_parts, n_local + 1) if batch is None else \
        (n_parts, batch, n_local + 1)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=shard)

    args = (
        arg((n_parts, rows), jnp.int32),
        arg((n_parts, rows, width), jnp.int32),
        arg((n_parts, rows, width), jnp.float32),
        arg(state, jnp.float32),
        arg(state, jnp.float32),
        arg(state, jnp.float32),
    )
    return fn.lower(*args).compile()


def device_bytes(compiled) -> int:
    """Arguments plus temporaries on each device."""
    mem = compiled.memory_analysis()
    return int(mem.argument_size_in_bytes + mem.temp_size_in_bytes)


@pytest.mark.parametrize("exchange", ["a2a", "pmin"])
def test_scale20_engine_fits_one_chip(topo, exchange):
    compiled = compile_engine(topo.devices[:1], exchange, **SCALE20)
    used = device_bytes(compiled)
    # the graph alone is R * (1 + 2W) words
    graph = 4 * SCALE20["rows"] * (1 + 2 * SCALE20["width"])
    assert graph <= used <= V5E_HBM_BYTES


def test_sparse_engine_fits_one_chip(topo):
    # rmat1(16, seed=0) shapes: the sparse program at scale 20 takes
    # about half a minute to compile
    compiled = compile_engine(
        topo.devices[:1], "sparse", n_local=1 << 16, rows=85_904, width=64
    )
    assert device_bytes(compiled) <= V5E_HBM_BYTES


def test_a2a_engine_on_four_chips(topo):
    # rmat1(20) over four ranks: a quarter of the rows, plus the
    # imbalance a 4-way block split shows at scale 16
    compiled = compile_engine(
        topo.devices, "a2a", n_local=1 << 18, rows=360_000, width=64
    )
    assert device_bytes(compiled) <= V5E_HBM_BYTES
    assert "all-to-all" in compiled.as_text()


@pytest.mark.parametrize("exchange", ["sparse", "a2a"])
def test_scopes_cover_the_superstep_on_four_chips(topo, push_chunk_loop,
                                                  exchange):
    # the exchange's all-to-all exists only across chips; 20,000 rows
    # make a frontier capacity (R/8) of several push chunks
    from bench import scopes

    text = compile_engine(
        topo.devices, exchange, n_local=1 << 10, rows=20_000, width=64
    ).as_text()
    assert "all-to-all" in text
    assert scopes.unscoped(text) == []
    assert {"eligibility", "relax/dense", "exchange", "vote"} <= set(
        scopes.op_phases(text).values()
    )
    # the push relax's chunk loop holds no collective: each chip runs
    # its own trip count, and its gathers and scatter-min are relax/push
    opcodes, loop_phases = push_chunk_loop(text)
    if exchange == "sparse":
        assert {"gather", "scatter"} <= opcodes
        assert loop_phases == {"relax/push"}
        assert not opcodes & {"all-to-all", "all-reduce", "all-gather",
                              "collective-permute"}
    else:
        assert not opcodes


def test_sparse_payload_crosses_chips_as_one_integer_all_to_all(topo):
    # one all-to-all a branch: the dense fallback's f32 candidates and
    # the sparse payload, whose indices and value bits travel as u32
    # words, which no float op on the way can flush to zero
    text = compile_engine(
        topo.devices, "sparse", n_local=1 << 12, rows=6_000, width=64
    ).as_text()
    types = sorted(line.split(" = ")[1].split("[")[0]
                   for line in text.splitlines() if " all-to-all(" in line)
    assert types == ["f32", "u32"]


def scatters(text):
    """(result elements, updates shape, op_name) of every scatter in a
    compiled module, read from the HLO types of its operands."""
    shapes = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = \w+\[([\d,]*)\]", line)
        if m:
            shapes[m.group(1)] = tuple(
                int(d) for d in m.group(2).split(",") if d)
    out = []
    for line in text.splitlines():
        m = re.search(r"%([\w.\-]+) = \w+\[[^\]]*\]\S* scatter\(([^)]*)\)",
                      line)
        if m:
            updates = m.group(2).split(",")[2].strip().lstrip("%")
            op_name = re.search(r'op_name="([^"]*)"', line)
            out.append((int(np.prod(shapes[m.group(1)])), shapes[updates],
                        op_name.group(1) if op_name else ""))
    return out


def test_sparse_exchange_scatters_remote_segments_only(topo):
    # each chip's own candidates stay out of the payload: the pack's
    # two planes (indices, value bits), each of P x (slot_cap + 1)
    # words, are handed the 3 remote segments of n_local candidates,
    # and the unpack's scatter-min the 3 received segments' slots
    P_, n_local, rows, width = 4, 1 << 12, 6_000, 64
    _, slot_cap = frontier_caps(rows, width, n_local, P_)
    found = scatters(compile_engine(
        topo.devices, "sparse", n_local=n_local, rows=rows, width=width
    ).as_text())
    pack = [u for n, u, _ in found if n == P_ * (slot_cap + 1)]
    assert pack == [(P_ - 1, n_local)] * 2
    unpack = [u for n, u, op in found
              if n == n_local + 1 and "/exchange/" in op]
    assert [int(np.prod(u)) for u in unpack] == [(P_ - 1) * slot_cap]
