"""The reduction of a profiler trace to busy time, idle share, op
times, collective time and labelled idle gaps."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import xplane

FIXTURE = Path(__file__).parent / "fixtures" / "cpu_window.xplane.pb"


def ev(name, start, dur, **stats):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=list(stats.items()))


def plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def tpu_profile(devices=1):
    """A window 0-100 ns: ops 0-10, 10-30 (with 12-20 nested), 50-60 on
    each chip; solves 0-40 and 45-100 on the host."""
    ops = [ev("fusion.1", 0, 10), ev("while.3", 10, 20),
           ev("all-to-all.2", 12, 8), ev("scatter.4", 50, 10)]
    host = [ev("bench.window", 0, 100), ev("bench.solve", 0, 40),
            ev("bench.solve", 45, 55), ev("PjitFunction(solve)", 62, 30)]
    return SimpleNamespace(planes=[
        *[plane(f"/device:TPU:{i}", **{"XLA Ops": ops, "Steps": []})
          for i in range(devices)],
        plane("/host:CPU", python=host, other=[ev("bench.ghost", 0, 1)]),
    ])


def test_op_names_keep_name_type_and_opcode():
    text = ("%fusion.6 = f32[1048577]{0:T(1024)S(1)} fusion(s32[9585344]"
            "{0:T(1024)S(1)} %fusion.15), kind=kCustom, calls=%f.20")
    assert xplane.op_name(text) == "%fusion.6 = f32[1048577] fusion"
    assert xplane.op_name("while.3") == "while.3"


def test_union_merges_overlaps():
    assert xplane._union([(5, 8), (0, 3), (2, 4), (8, 9)]) == [[0, 4], [5, 9]]


def test_self_time_leaves_out_nested_events():
    t = xplane._self_times([(10, 30, "while"), (12, 20, "a"),
                            (20, 25, "b"), (40, 50, "c")])
    assert t == {"while": 7, "a": 8, "b": 5, "c": 10}


def test_summary_of_a_tpu_shaped_trace():
    s = xplane.summarize(tpu_profile())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)
    assert s.idle_pct == pytest.approx(60.0)
    assert s.op_s["while.3"] == pytest.approx(12e-9)
    assert s.collective_s == pytest.approx(8e-9)
    # the gap 60-100 lies in the second solve, inside PjitFunction at
    # its midpoint 80; the gap 30-50 sits between the solves
    assert s.gaps == [("bench.solve>PjitFunction(solve)", 40e-9),
                      ("bench.window", 20e-9)]


def test_busy_time_is_a_mean_over_chips_and_gaps_name_their_chip():
    s = xplane.summarize(tpu_profile(devices=2))
    assert s.devices == 2
    assert s.busy_s == pytest.approx(40e-9)
    assert s.op_s["fusion.1"] == pytest.approx(10e-9)
    assert {g[0].split(":")[0] for g in s.gaps} == {"chip0", "chip1"}


def test_no_window_span_or_no_device_op_reads_nothing():
    p = tpu_profile()
    p.planes[-1].lines[0].events = p.planes[-1].lines[0].events[1:]
    assert xplane.summarize(p) is None
    q = tpu_profile()
    q.planes = q.planes[1:]
    assert xplane.summarize(q) is None


def test_summary_of_a_recorded_cpu_trace():
    s = xplane.summarize(xplane.load(FIXTURE))
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_pct < 100
    assert s.op_s and all(t >= 0 for t in s.op_s.values())
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s, rel=0.05)
    # the engine's votes are all-reduces even over a one-device mesh
    assert 0 < s.collective_s < s.busy_s
    assert 0 < len(s.gaps) <= 10
    assert all(label.startswith("bench.") for label, _ in s.gaps)
    assert [g[1] for g in s.gaps] == sorted((g[1] for g in s.gaps),
                                            reverse=True)
