"""The plain reference and the bfloat16 control."""

import ml_dtypes
import numpy as np
import pytest

from bench.reference import Reference, dijkstra_bf16, mismatches


def symmetric(n, edges):
    src = [a for a, b, _ in edges] + [b for a, b, _ in edges]
    dst = [b for a, b, _ in edges] + [a for a, b, _ in edges]
    w = [x for *_, x in edges] * 2
    return (n, np.array(src, np.int32), np.array(dst, np.int32),
            np.array(w, np.float32))


def test_distances_and_reach_on_a_small_graph():
    g = symmetric(5, [(0, 1, 2), (1, 2, 3), (0, 2, 10), (3, 4, 1)])
    ref = Reference(*g)
    assert ref.distances(0).tolist() == [0, 2, 5, np.inf, np.inf]
    assert ref.reach(0) == (6, 3)
    assert ref.reach(4) == (2, 2)


def test_reference_refuses_repeated_pairs():
    n, src, dst, w = symmetric(3, [(0, 1, 2), (0, 1, 5)])
    with pytest.raises(ValueError):
        Reference(n, src, dst, w)


def test_mismatches_counts_vertices_that_differ():
    ref = np.array([0.0, 2.0, np.inf])
    assert mismatches(np.array([0, 2, np.inf], np.float32), ref) == 0
    assert mismatches(np.array([0, 3, np.inf], np.float32), ref) == 1
    assert mismatches(np.array([0, 2, 7], np.float32), ref) == 1
    assert mismatches(np.zeros(2, np.float32), ref) == 3


def test_bf16_control_is_exact_below_256_and_rounds_above():
    g = symmetric(4, [(0, 1, 200), (1, 2, 57), (0, 3, 100)])
    exact = Reference(*g).distances(0)
    low = dijkstra_bf16(*g, 0)
    assert low.dtype == np.float32  # only the values can fail the check
    assert exact[2] == 257
    assert mismatches(low, exact) == 1  # 257 is not a bfloat16
    assert low[[0, 1, 3]].tolist() == [0, 200, 100]
    assert np.array_equal(low, low.astype(ml_dtypes.bfloat16).astype(np.float32))
