"""The TEPS and roofline arithmetic, and the table of peaks."""

import pytest

from bench import work
from bench.peaks import PEAKS, UnknownDevice, peaks_for


def test_teps_is_reached_edges_over_window_seconds():
    assert work.teps(31_403_422, 11.0) == pytest.approx(2_854_856.545454)
    with pytest.raises(ValueError):
        work.teps(10, 0.0)


def test_least_bytes_counts_each_reached_edge_and_vertex_once():
    assert work.least_bytes(10, 5) == 8 * 10 + 8 * 5


def test_roofline_is_least_time_over_busy_time():
    # 819 MB at 819 GB/s takes 1 ms; 2 ms of device time is half the roof
    edges = 819_000_000 // 8
    assert work.roofline_pct(edges, 0, 0.002, 819e9) == pytest.approx(50.0)
    assert work.roofline_pct(edges, 0, 0.0, 819e9) is None


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert all("source" in p for p in PEAKS.values())
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
