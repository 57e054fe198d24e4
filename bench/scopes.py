"""Device time of the engine's superstep by phase.

The engine (``repro.core.engine.build_step``) runs each step of a
superstep under a ``jax.named_scope``, so every op of the loop body
carries its phase in its HLO ``op_name`` metadata
(``jit(solve)/while/body/relax/cond/branch_0_fun/push/...``).  A
profiler trace names each op it ran, on TPU by its whole HLO
instruction and on the CPU by the instruction's name; neither carries
the ``op_name``.  So the phase of each op comes from the text of the
compiled engine: instruction name -> ``op_name`` -> the innermost of
:data:`PHASES` named in it.  The engine is compiled again, after the
window, through the program's own ``Solver``, for the cell's spec,
graph and chips; the window's trace is re-read from the cell's newest
``.xplane.pb`` and its device self time summed per phase over the
``bench.window`` interval, a mean per chip.

The host half: the program's spans are profiler annotations, so the
trace holds ``solver.fingerprint``, ``solver.initial_state`` and
``solver.unpermute`` inside each ``bench.solve``, on the host clock.

A program without the scopes or the spans reads as nothing (None).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Optional

from bench import harness, xplane

#: the superstep's phases, as the engine names its scopes
PHASES = ("eligibility", "compact", "relax", "exchange", "vote")
#: the relax phase's two sub-scopes
RELAX_SCOPES = ("push", "dense")
#: the program's host spans of a solve's work outside the device
HOST_SPANS = ("solver.fingerprint", "solver.initial_state",
              "solver.unpermute")

#: the checkout whose ``bench/.traces`` the readers look in
ROOT = Path(__file__).resolve().parents[1]

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation"
    r"|false_computation)=%([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}"
)
_BODY = re.compile(r"\bbody=%([\w.\-]+)")
_BRACES = re.compile(r"\{[^{}]*\}")
_REF = re.compile(r"%([\w.\-]+)")


def phase(op_name: str) -> Optional[str]:
    """The innermost phase named in ``op_name``; the relax phase with
    its sub-scope (``relax/push``) where one follows it."""
    parts = op_name.split("/")
    at = [i for i, p in enumerate(parts) if p in PHASES]
    if not at:
        return None
    name = parts[at[-1]]
    sub = next((p for p in parts[at[-1] + 1:] if p in RELAX_SCOPES), None)
    return f"{name}/{sub}" if sub else name


def phases_named(op_name: str) -> list:
    """Every phase ``op_name`` names, outermost first."""
    return [p for p in op_name.split("/") if p in PHASES]


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    op_name: str
    called: list            # names of the computations it calls
    body: Optional[str]     # a ``while``'s body computation
    operands: list          # names of the instructions it reads


def _opcode(rest: str) -> str:
    """The opcode of an instruction, from the text after ``= ``: the
    result type (a tuple in parentheses, or one word once the layouts
    in braces are gone), then ``opcode(``."""
    while _BRACES.search(rest):
        rest = _BRACES.sub("", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = "x" + rest[i + 1:]
                break
    m = re.match(r"\S+ ([\w\-]+)\(", rest)
    return m.group(1) if m else ""


def parse_hlo(text: str) -> dict:
    """The instructions of an HLO module's text, by computation:
    ``{computation: [Instr]}``."""
    out: dict = {}
    current = None
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c and not line.startswith(" "):
            current = out.setdefault(c.group(1), [])
            continue
        m = _INSTR.match(line)
        if m is None or current is None:
            continue
        called = []
        for one, many in _CALLED.findall(m.group(2)):
            called += [one] if one else [
                x.strip().lstrip("%") for x in many.split(",")]
        op = _OP_NAME.search(m.group(2))
        body = _BODY.search(m.group(2))
        refs = _REF.findall(m.group(2).split("), ")[0])
        current.append(Instr(m.group(1), _opcode(m.group(2)),
                             op.group(1) if op else "", called,
                             body.group(1) if body else None,
                             [r for r in refs if r not in called]))
    return out


def loop_body_instructions(text: str) -> list:
    """Every instruction of the computations a ``while`` body calls,
    directly or through fusions, conditionals and reducers, the body
    included."""
    return _loop_body(parse_hlo(text))


def _loop_body(comps: dict) -> list:
    todo = [i.body for instrs in comps.values() for i in instrs if i.body]
    seen, out = set(), []
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        out += comps[c]
        todo += [x for i in comps[c] for x in i.called]
    return out


def op_phases(text: str) -> dict:
    """``{instruction name: phase}`` for the instructions of a compiled
    module's loop body.  An instruction's phase is the one its own
    ``op_name`` names.  The compiler makes instructions with no
    ``op_name`` (a fusion's new root, a copy, a change of layout, a
    loop of its own), and JAX lowers some operations (``cumsum``)
    outside the caller's name stack.  Such an instruction takes, in
    this order: the phase most of the instructions it fuses name; the
    phase of the first instruction that reads it; of the first it
    reads; of the instruction whose computation it is in."""
    comps = parse_hlo(text)
    body = {i.name: i for i in _loop_body(comps)}
    readers: dict = {}
    caller: dict = {}
    for instrs in comps.values():
        for i in instrs:
            for o in i.operands:
                readers.setdefault(o, []).append(i.name)
            for c in i.called:
                for j in comps.get(c, []):
                    caller.setdefault(j.name, i.name)
    memo: dict = {}

    def fused(comp: str) -> list:
        out = []
        for i in comps.get(comp, []):
            own = phase(i.op_name)
            out += [own] if own else [p for c in i.called for p in fused(c)]
        return out

    def first(names) -> Optional[str]:
        return next((p for p in map(of, names) if p), None)

    def of(name: str) -> Optional[str]:
        if name in memo or name not in body:
            return memo.get(name)
        memo[name] = None  # a cycle reads nothing
        i = body[name]
        got = phase(i.op_name)
        if got is None and i.opcode == "fusion":
            inner = [p for c in i.called for p in fused(c)]
            got = max(set(inner), key=inner.count) if inner else None
        got = (got or first(readers.get(name, ())) or first(i.operands)
               or first([caller[name]] if name in caller else []))
        memo[name] = got
        return got

    return {name: p for name in body if (p := of(name)) is not None}


#: opcodes whose device time can matter
COSTLY = ("fusion", "scatter", "gather", "sort", "reduce", "all-to-all",
          "all-reduce")


def unscoped(text: str) -> list:
    """The loop body's costly instructions that the scopes do not
    cover: one whose ``op_name`` lies in the loop body yet names no
    phase or more than one, or one :func:`op_phases` gives no phase."""
    phases_of = op_phases(text)
    return [i for i in loop_body_instructions(text) if i.opcode in COSTLY
            and (i.name not in phases_of
                 or ("/while/body/" in i.op_name
                     and len(phases_named(i.op_name)) != 1))]


# ---------------------------------------------------------------------
# the engine the window ran, compiled again
# ---------------------------------------------------------------------


def engine_text(run) -> str:
    """The compiled text of the cell's engine: the configuration's spec
    on the cell's chips, for the run's graph, through the program's
    ``Solver`` (its partition, its compiled engine)."""
    from repro.api import Solver, get_processing
    from repro.core.engine import initial_state
    from repro.launch.mesh import make_local_topology

    cell = run.setup.cell
    solver = Solver(cell.config["spec"],
                    mesh=make_local_topology(cell.chips).mesh)
    pg = solver.partition(run.setup.graph)
    fn = solver.compiled(pg.n_parts, pg.n_local)
    state = initial_state(pg, get_processing("sssp"), [])
    return fn.lower(*pg.on_mesh(solver.mesh), *state).compile().as_text()


# ---------------------------------------------------------------------
# the trace, by phase
# ---------------------------------------------------------------------


def trace_file(cell: str, root: Path) -> Optional[Path]:
    """The cell's newest profiler trace under ``root``, if any."""
    pbs = sorted((Path(root) / harness.TRACE_DIR / cell).glob(
        "**/*.xplane.pb"))
    return pbs[-1] if pbs else None


def _instruction(event_name: str) -> str:
    """The instruction an op event names: ``fusion.6`` of
    ``%fusion.6 = f32[1048577]{0} fusion(...)`` (TPU) or of
    ``fusion.6`` (CPU)."""
    return event_name.split(" = ")[0].strip().lstrip("%")


@dataclasses.dataclass
class Split:
    """What one traced window spent, by phase and by host span."""
    phase_s: dict          # phase -> device self seconds, mean per chip
    host_s: dict           # host span -> seconds, summed over solves


def split(profile, phases_of: dict,
          window_span: str = "bench.window") -> Optional[Split]:
    """Device self time per phase (``phases_of``: instruction ->
    phase) and host time per program span, over ``window_span``."""
    ops, host = xplane.read_events(profile)
    windows = [(s, e) for s, e, n in host if n == window_span]
    if not ops or not windows:
        return None
    lo, hi = windows[0]
    phase_s: dict = defaultdict(float)
    for evs in ops.values():
        evs = [(max(s, lo), min(e, hi), n) for s, e, n in evs
               if e > lo and s < hi]
        for name, t in xplane._self_times(evs).items():
            p = phases_of.get(_instruction(name))
            if p is not None:
                phase_s[p] += t / 1e9 / len(ops)
    host_s: dict = defaultdict(float)
    for name in HOST_SPANS:
        merged = xplane._union((max(s, lo), min(e, hi))
                               for s, e, n in host
                               if n == name and e > lo and s < hi)
        host_s[name] = sum(e - s for s, e in merged) / 1e9
    return Split(dict(phase_s), dict(host_s))


_CACHE: dict = {}


def of_run(run) -> Optional[Split]:
    """The split of the run's traced window, read once per trace."""
    if run.trace is None:
        return None
    pb = trace_file(run.cell.name, ROOT)
    if pb is None:
        return None
    key = (str(pb), pb.stat().st_mtime_ns)
    if key not in _CACHE:
        try:
            phases_of = op_phases(engine_text(run))
        except Exception as e:  # a program it cannot compile reads nothing
            harness.say(f"scopes: no engine text: {type(e).__name__}: {e}")
            phases_of = {}
        _CACHE.clear()
        _CACHE[key] = split(xplane.load(pb), phases_of)
    return _CACHE[key]


def solve_host_ms(run) -> Optional[float]:
    """Host milliseconds a solve spends in :data:`HOST_SPANS`, over
    the window's solves; None when the trace holds none of them."""
    s = of_run(run)
    if s is None or not any(s.host_s.values()) or not run.window.solves:
        return None
    return 1e3 * sum(s.host_s.values()) / len(run.window.solves)


def ms_per_superstep(run, phases: tuple) -> Optional[float]:
    """Device milliseconds per superstep in ``phases`` (a phase also
    covers its sub-scopes); None without phases in the trace."""
    s = of_run(run)
    steps = sum(r.supersteps for r in run.window.solves if not r.error)
    if s is None or not s.phase_s or steps == 0:
        return None
    t = sum(v for k, v in s.phase_s.items() if k.split("/")[0] in phases)
    return 1e3 * t / steps
