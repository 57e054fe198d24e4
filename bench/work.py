"""The arithmetic of the benchmark's rates: Graph500's TEPS and the
least memory traffic any exact SSSP needs, from which the engine's
roofline share follows."""

from __future__ import annotations

#: bytes a solve must move per reached edge: one read of its
#: destination (int32) and weight (float32)
BYTES_PER_EDGE = 8
#: bytes per reached vertex: one read and one write of its float32
#: distance
BYTES_PER_VERTEX = 8


def teps(reached_edges: int, window_s: float) -> float:
    """Traversed edges per second: the directed edges whose source the
    window's solves reached, over the window's seconds."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return reached_edges / window_s


def least_bytes(reached_edges: int, reached_vertices: int) -> int:
    """HBM bytes that no exact single-source solve can do without,
    whatever implements it."""
    return BYTES_PER_EDGE * reached_edges + BYTES_PER_VERTEX * reached_vertices


def roofline_pct(reached_edges: int, reached_vertices: int,
                 busy_s: float, hbm_bytes_per_s: float) -> float | None:
    """Share of the memory roofline: the least time the least bytes
    take at the HBM peak, over the device time the work took.  None
    when there is no device time to divide by."""
    if busy_s <= 0:
        return None
    least_s = least_bytes(reached_edges, reached_vertices) / hbm_bytes_per_s
    return 100.0 * least_s / busy_s
