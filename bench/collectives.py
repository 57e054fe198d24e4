"""Bytes the engine's collectives send from each chip to the others,
for the share of the inter-chip roofline they reach.

One run of an all-to-all over P chips keeps one of the P blocks of its
operand and sends the other P - 1, so a chip sends (P - 1)/P of the
operand's bytes; an all-reduce sends at least as much (a ring sends
twice that), so the same count is a floor for it.  The operand's
bytes are read from the instruction's HLO type (an all-to-all's and an
all-reduce's result has the type of their operands, a tuple of them
when there are several).  A TPU trace names each op by its instruction
text, type included; a CPU trace names only the instruction, whose
type then comes from the compiled engine's text.
"""

from __future__ import annotations

import re

from bench import scopes, xplane

#: bytes of one element, by HLO element type
ELEMENT_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
#: opcodes whose operands leave the chip: each run counted once (the
#: start of an asynchronous all-reduce, never its done)
SENDS = ("all-to-all", "all-reduce", "all-reduce-start")

_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def operand_bytes(result_type: str) -> int:
    """Bytes of the arrays an HLO type names: ``u32[4,1,8]{2,1,0}`` or
    a tuple ``(s32[], s32[2])``."""
    total = 0
    for elem, dims in _ARRAY.findall(result_type):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * ELEMENT_BYTES.get(elem, 0)
    return total


def sent_bytes(instruction: str, n_parts: int):
    """Bytes one run of ``instruction`` (HLO text from its ``%name = ``
    on, as a module's line or a TPU trace's op name) sends from a chip
    to the other ``n_parts - 1``; None for any other instruction, or
    text without the type."""
    m = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*)$", instruction)
    if m is None:
        return None
    rest = m.group(1)
    opcode = scopes._opcode(rest if "(" in rest else rest + "(")
    if opcode not in SENDS:
        return None
    result_type = rest.split(f" {opcode}")[0]
    return operand_bytes(result_type) * (n_parts - 1) // n_parts


def module_bytes(text: str, n_parts: int) -> dict:
    """``{instruction name: bytes a run sends}`` for the collectives of
    a compiled module's text."""
    out = {}
    for line in text.splitlines():
        m = scopes._INSTR.match(line)
        if m is None:
            continue
        b = sent_bytes(line.split(", metadata=")[0], n_parts)
        if b is not None:
            out[m.group(1)] = b
    return out


def runs_in_window(profile, window_span: str = "bench.window") -> dict:
    """``{op name: runs a chip, mean over chips}`` of the collective
    ops that started inside ``window_span``; None without the span or
    without device ops."""
    ops, host = xplane.read_events(profile)
    windows = [(s, e) for s, e, n in host if n == window_span]
    if not ops or not windows:
        return None
    lo, hi = windows[0]
    out: dict = {}
    for evs in ops.values():
        for s, _, name in evs:
            if lo <= s < hi and xplane.COLLECTIVE.search(name):
                out[name] = out.get(name, 0) + 1 / len(ops)
    return out


def bytes_sent(run):
    """Bytes a chip sent to the others in the traced window, a mean
    over chips: each collective's bytes a run times its runs.  None
    when the run has no trace with collectives in it, or the types of
    its collectives cannot be read."""
    pb = scopes.trace_file(run.cell.name, scopes.ROOT)
    if run.trace is None or pb is None:
        return None
    runs = runs_in_window(xplane.load(pb))
    if not runs:
        return None
    parts = run.trace.devices
    typed = {n: sent_bytes(n, parts) or 0 for n in runs if " = " in n}
    if len(typed) < len(runs):  # ops named without their type
        try:
            table = module_bytes(scopes.engine_text(run), parts)
        except Exception as e:  # a program it cannot compile reads nothing
            scopes.harness.say(f"collectives: no engine text: "
                               f"{type(e).__name__}: {e}")
            return None
        typed.update({n: table.get(scopes._instruction(n), 0)
                      for n in runs if n not in typed})
    return sum(typed[n] * k for n, k in runs.items())
