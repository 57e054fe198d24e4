"""Shared fixtures.  NOTE: no XLA device-count flags here — unit and
smoke tests must see the real (single) device; multi-device tests run
in subprocesses that set their own flags."""

import jax
import pytest


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def tiny_graphs():
    from repro.graph import rmat1, rmat2, grid_road_graph, small_world_graph

    return [
        rmat1(8, seed=3),
        rmat2(8, seed=5),
        grid_road_graph(12, seed=1),
        small_world_graph(300, seed=2),
    ]


@pytest.fixture(scope="session")
def topo1():
    from repro.models.common import single_device_topology

    return single_device_topology()


@pytest.fixture(scope="session")
def push_chunk_loop():
    """A function of a compiled engine's text: the opcodes reached from
    the body of the push relax's chunk loop (its ``while`` under the
    ``relax/push`` scope), through fusions and called computations,
    and the phases ``bench.scopes.op_phases`` gives its costly ops."""
    from bench import scopes

    def read(text):
        comps = scopes.parse_hlo(text)
        phases = scopes.op_phases(text)
        todo = [i.body for instrs in comps.values() for i in instrs
                if i.opcode == "while"
                and scopes.phase(i.op_name) == "relax/push"]
        seen, ops = set(), []
        while todo:
            c = todo.pop()
            if c in seen or c not in comps:
                continue
            seen.add(c)
            ops += comps[c]
            todo += [x for i in comps[c] for x in i.called]
        costly = {phases.get(i.name) for i in ops
                  if i.opcode in scopes.COSTLY}
        return {i.opcode for i in ops}, costly

    return read
