"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: device busy time and idle share over the traced
window, device time per operation, time in collectives, and the
longest idle gaps, each labelled with the host span open during it.

A TPU trace has a plane ``/device:TPU:<i>`` per chip, whose line
``XLA Ops`` holds one event per operation run, on the host's clock.
The CPU backend has no device plane: its operations are events on host
threads that carry an ``hlo_op`` stat, which lets the same reduction
be tested on a trace recorded on a CPU.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

#: operations that move data between chips
COLLECTIVE = re.compile(
    r"all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|\bsend\b|\brecv\b|send-done|recv-done"
)
#: prefix of the benchmark's own host spans
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: int
    busy_s: float                  # mean over devices
    op_s: dict                     # op name -> self seconds, mean per device
    collective_s: float            # mean per device
    gaps: list                     # [(label, seconds)], longest first

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events) -> dict:
    """Per op name, its duration less that of the events nested in it
    (one line of one device; a control-flow op can enclose others)."""
    out = defaultdict(float)
    stack: list = []  # [end, name, time of nested events, start]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm, kids, start = stack.pop()
            out[nm] += (end - start) - kids
        if stack:
            stack[-1][2] += min(e, stack[-1][0]) - s
        stack.append([e, name, 0.0, s])
    while stack:
        end, nm, kids, start = stack.pop()
        out[nm] += (end - start) - kids
    return out


def op_name(event_name: str) -> str:
    """A TPU op event is named by its whole HLO instruction; keep its
    name, result type and opcode (``%fusion.6 = f32[1048577] fusion``)."""
    head = re.sub(r"\{[^{}]*\}", "", event_name).split("(")[0]
    return head.strip()


def read_events(profile):
    """(ops, host): ops as {device: [(start_ns, end_ns, name)]}; host
    the events, as [(start_ns, end_ns, name)], of the host thread that
    opened the benchmark's spans."""
    ops, host = defaultdict(list), []
    device_planes = [p for p in profile.planes
                     if p.name.startswith("/device:")
                     and any(ln.name == "XLA Ops" for ln in p.lines)]
    for i, plane in enumerate(device_planes):
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops[i].extend((e.start_ns, e.start_ns + e.duration_ns,
                               op_name(e.name)) for e in line.events)
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            has_spans = any(e.name.startswith(SPAN_PREFIX) for e in events)
            for e in events:
                end = e.start_ns + e.duration_ns
                st = {} if device_planes else _stats(e)
                if "hlo_op" in st:
                    dev = int(st.get("device_ordinal", 0))
                    ops[dev].append((e.start_ns, end, e.name))
                elif has_spans:
                    # the benchmark's spans and what the host did
                    # inside them, on the thread that opened them
                    host.append((e.start_ns, end, e.name))
    return dict(ops), host


def summarize(profile, window_span: str = "bench.window",
              top_gaps: int = 10) -> TraceSummary | None:
    """The trace's numbers over the interval of the host span
    ``window_span``; None when the trace holds no device operation or
    no such span."""
    ops, host = read_events(profile)
    windows = [(s, e) for s, e, n in host if n == window_span]
    if not ops or not windows:
        return None
    lo, hi = windows[0]
    window_s = (hi - lo) / 1e9
    n_dev = len(ops)
    busy = 0.0
    op_s = defaultdict(float)
    collective = 0.0
    gaps = []
    for dev, evs in ops.items():
        evs = [(max(s, lo), min(e, hi), n) for s, e, n in evs
               if e > lo and s < hi]
        merged = _union((s, e) for s, e, _ in evs)
        busy += sum(e - s for s, e in merged) / 1e9
        for name, t in _self_times(evs).items():
            op_s[name] += t / 1e9 / n_dev
            if COLLECTIVE.search(name):
                collective += t / 1e9 / n_dev
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((b - a, a, dev) for a, b in zip(edges[::2], edges[1::2])
                    if b > a)
    gaps = sorted(gaps, reverse=True)[:top_gaps]
    return TraceSummary(
        window_s=window_s, devices=n_dev, busy_s=busy / n_dev,
        op_s=dict(op_s), collective_s=collective,
        gaps=[(_label(host, a + d / 2, dev, n_dev), d / 1e9)
              for d, a, dev in gaps],
    )


def _label(host, t, dev, n_dev) -> str:
    """The innermost benchmark span open at ``t``, and the innermost
    host event inside it, as ``bench.solve>name``."""
    open_ = [(s, n) for s, e, n in host if s <= t < e]
    ours = [x for x in open_ if x[1].startswith(SPAN_PREFIX)]
    name = max(ours)[1] if ours else "no span"
    theirs = [x for x in open_ if not x[1].startswith(SPAN_PREFIX)
              and (not ours or x[0] >= max(ours)[0])]
    if theirs:
        name += ">" + max(theirs)[1]
    return f"chip{dev}:{name}" if n_dev > 1 else name


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))
