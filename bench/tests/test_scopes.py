"""The engine's phases and the program's host spans, read from a trace:
the HLO parser, the phase of each instruction, and the four metric
files on a CPU trace recorded here."""

import json
import shutil
import time
from pathlib import Path

import pytest

from bench import harness, scopes, xplane

FIXTURE = Path(__file__).parent / "fixtures" / "newcell"
CPU_PEAKS = {"hbm_bytes_per_s": 100e9}
NEW = ("relax_ms_per_superstep", "frontier_ms_per_superstep",
       "exchange_ms_per_superstep", "solve_host_ms")

#: a loop whose body holds a scoped fusion, a fusion the compiler gave
#: no op_name (its fused ops name the phase), a copy that only a reader
#: names, and an op outside any scope
HLO = """\
HloModule jit_solve, is_scheduled=true

%fused_a (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p0), metadata={op_name="jit(solve)/while/body/relax/cond/branch_0_fun/push/neg"}
}

%fused_b (p1: f32[8]) -> f32[8] {
  %p1 = f32[8]{0} parameter(0)
  ROOT %abs.1 = f32[8]{0} abs(%p1), metadata={op_name="jit(solve)/while/body/exchange/abs"}
}

%body (arg: (f32[8], s32[])) -> (f32[8], s32[]) {
  %arg = (f32[8]{0}, s32[]) parameter(0)
  %gte.0 = f32[8]{0} get-tuple-element(%arg), index=0
  %fusion.1 = f32[8]{0:T(1024)S(1)} fusion(%gte.0), kind=kLoop, calls=%fused_a, metadata={op_name="jit(solve)/while/body/relax/cond/branch_0_fun/push/neg"}
  %copy.2 = f32[8]{0} copy(%fusion.1)
  %fusion.3 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_b
  %gte.1 = s32[] get-tuple-element(%arg), index=1
  ROOT %tuple.4 = (f32[8]{0}, s32[]) tuple(%fusion.3, %gte.1)
}

%cond (c: (f32[8], s32[])) -> pred[] {
  %c = (f32[8]{0}, s32[]) parameter(0)
  ROOT %constant.9 = pred[] constant(true)
}

ENTRY %main (x: f32[8]) -> (f32[8], s32[]) {
  %x = f32[8]{0} parameter(0)
  %constant.0 = s32[] constant(0)
  %tuple.5 = (f32[8]{0}, s32[]) tuple(%x, %constant.0)
  ROOT %while.6 = (f32[8]{0}, s32[]) while(%tuple.5), condition=%cond, body=%body
}
"""


def test_phase_is_the_innermost_scope_with_its_relax_sub_scope():
    body = "jit(solve)/while/body"
    assert scopes.phase(f"{body}/eligibility/reduce_min") == "eligibility"
    assert scopes.phase(f"{body}/relax/cond/branch_1_fun/dense/scatter-min") \
        == "relax/dense"
    assert scopes.phase(f"{body}/exchange/cond/branch_0_fun/all-to-all") \
        == "exchange"
    assert scopes.phase(f"{body}/add") is None
    assert scopes.phase("reduce_window_sum") is None
    assert scopes.phases_named(f"{body}/relax/vote/add") == ["relax", "vote"]


def test_opcodes_past_layouts_and_tuple_types():
    assert scopes._opcode("f32[1048577]{0:T(1024)S(1)} fusion(s32[9] %a)") \
        == "fusion"
    assert scopes._opcode("(f32[8]{0}, /*index=5*/s32[]) while(%t), body=%b") \
        == "while"
    assert scopes._opcode("s32[] parameter(0)") == "parameter"


def test_every_instruction_of_the_loop_gets_a_phase():
    comps = scopes.parse_hlo(HLO)
    assert set(comps) == {"fused_a", "fused_b", "body", "cond", "main"}
    (loop,) = [i for i in comps["main"] if i.opcode == "while"]
    assert loop.body == "body" and loop.operands == ["tuple.5"]
    names = {i.name for i in scopes.loop_body_instructions(HLO)}
    assert {"fusion.1", "neg.1", "abs.1", "tuple.4"} <= names
    assert "while.6" not in names and "constant.9" not in names
    phases = scopes.op_phases(HLO)
    assert phases["fusion.1"] == "relax/push"   # its own op_name
    assert phases["fusion.3"] == "exchange"     # what it fuses
    assert phases["copy.2"] == "exchange"       # what reads it
    assert "x" not in phases and "while.6" not in phases
    assert scopes.unscoped(HLO) == []


def test_an_unscoped_step_in_the_loop_body_is_found():
    text = HLO.replace('"jit(solve)/while/body/exchange/abs"',
                       '"jit(solve)/while/body/abs"')
    text = text.replace("calls=%fused_b",
                        'calls=%fused_b, metadata={op_name='
                        '"jit(solve)/while/body/abs"}')
    assert [i.name for i in scopes.unscoped(text)] == ["fusion.3"]


def test_instruction_of_a_trace_event():
    assert scopes._instruction(
        "%fusion.6 = f32[1048577]{0:T(1024)S(1)} fusion(%a), calls=%f") \
        == "fusion.6"
    assert scopes._instruction("wrapped_reduce-window.10") \
        == "wrapped_reduce-window.10"


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """One traced run of the fixture's one-chip cell on the CPU, with
    the four metrics appended to the copy's per-layer list."""
    root = tmp_path / "checkout"
    shutil.copytree(FIXTURE, root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"] += [
        {"name": n, "unit": "ms", "better": "lower",
         "source": "host_clock" if n == "solve_host_ms" else "device_trace",
         "layer": "test", "moves": "teps", "workloads": ["tiny.sparse"]}
        for n in NEW]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(scopes, "ROOT", root)
    r = harness.run(root, "tiny.sparse", 2**31 + 7, 0.5, True,
                    time.perf_counter(), look_for_chip=False,
                    peaks=CPU_PEAKS)
    return root, r


def test_the_metric_files_read_a_cpu_trace(traced):
    root, r = traced
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(NEW) <= set(m)
    device = [m[n] for n in NEW[:3]]
    assert all(v >= 0 for v in device)
    assert m["relax_ms_per_superstep"] > 0
    assert m["frontier_ms_per_superstep"] > 0
    assert sum(device) <= m["superstep_ms"]
    assert m["solve_host_ms"] > 0
    # the host metric is the program's own spans, each inside a solve
    _, host = xplane.read_events(xplane.load(scopes.trace_file(
        "tiny.sparse", root)))
    solves = [(s, e) for s, e, n in host if n == "bench.solve"]
    for name in scopes.HOST_SPANS:
        spans = [(s, e) for s, e, n in host if n == name]
        assert spans
        assert all(any(a <= s and e <= b for a, b in solves)
                   for s, e in spans)


def test_the_idle_gaps_name_a_program_span(traced):
    root, r = traced
    assert r["breakdown"]["idle_gaps"]
    # every gap, not the longest ten: on a loaded CPU any may lead
    s = xplane.summarize(xplane.load(scopes.trace_file("tiny.sparse", root)),
                         top_gaps=10**6)
    assert any(g.startswith("bench.solve>solver.") for g, _ in s.gaps)
