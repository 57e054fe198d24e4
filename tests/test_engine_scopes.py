"""The engine's named scopes cover its superstep.

Every op of the compiled ``while`` body, and of every computation it
calls, whose cost can matter carries one phase (eligibility, compact,
relax, exchange, vote), read with the parser the benchmark's per-phase
metrics use.  An unscoped step added to ``build_step`` fails here.
"""

import pytest

from bench import scopes
from repro.api import Solver, get_processing
from repro.api.solver import engine_cache_clear
from repro.core import frontier
from repro.core.engine import initial_state


def engine_text(spec, graph):
    s = Solver(spec)
    pg = s.partition(graph)
    fn = s.compiled(pg.n_parts, pg.n_local)
    state = initial_state(pg, get_processing("sssp"), [])
    return fn.lower(*pg.on_mesh(s.mesh), *state).compile().as_text()


@pytest.mark.parametrize("exchange", ["sparse", "a2a"])
def test_scopes_cover_the_superstep(tiny_graphs, push_chunk_loop, exchange,
                                    monkeypatch):
    # chunks of 8 rows, so the tiny graph's frontier capacity takes
    # several trips of the push relax's loop (one trip over the whole
    # capacity is loop-invariant, and the compiler hoists its gathers)
    monkeypatch.setattr(frontier, "PUSH_CHUNK_ROWS", 8)
    engine_cache_clear()
    try:
        text = engine_text(f"delta:5+buffer/{exchange}", tiny_graphs[0])
    finally:
        engine_cache_clear()
    assert scopes.unscoped(text) == []
    body = scopes.loop_body_instructions(text)
    costly = [i for i in body if i.opcode in scopes.COSTLY]
    assert costly
    # an op lowered from the step body names exactly one phase
    in_body = [i for i in costly if "/while/body/" in i.op_name]
    assert in_body
    assert all(len(scopes.phases_named(i.op_name)) == 1 for i in in_body)
    phases = set(scopes.op_phases(text).values())
    # one device: either exchange is the identity and compiles away
    # (the sparse one keeps only its votes and counters: a device's own
    # candidates never enter the payload, and it has no others)
    want = {"eligibility", "relax/dense", "vote"}
    if exchange == "sparse":
        want |= {"compact", "relax/push"}
    assert want <= phases
    moved = [i.opcode for instrs in scopes.parse_hlo(text).values()
             for i in instrs if scopes.phase(i.op_name) == "exchange"
             and i.opcode in ("scatter", "gather", "all-to-all")]
    assert moved == []
    # the push relax's chunk loop: its gathers and scatter-min, nested
    # in the superstep, all read as relax/push
    opcodes, loop_phases = push_chunk_loop(text)
    if exchange == "sparse":
        assert {"gather", "scatter"} <= opcodes
        assert loop_phases == {"relax/push"}
    else:
        assert not opcodes
