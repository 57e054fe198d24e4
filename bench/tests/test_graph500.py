"""The benchmark's copy of the Kronecker generator and of the search-key
draw."""

import numpy as np
import pytest

from bench import graph500

RMAT1 = dict(kind="kronecker", a=0.57, b=0.19, c=0.19, edge_factor=16,
             weight_max=100, graph_seed=0)


@pytest.fixture(scope="module")
def graph():
    return graph500.make_graph(RMAT1, 10)


def test_graph_is_symmetric_simple_and_weighted_as_configured(graph):
    n, src, dst, w = graph
    assert n == 1 << 10
    assert not np.any(src == dst)
    pairs = set(zip(src.tolist(), dst.tolist()))
    assert len(pairs) == len(src)
    assert pairs == set(zip(dst.tolist(), src.tolist()))
    assert w.dtype == np.float32
    assert np.all(w == np.round(w)) and w.min() >= 1 and w.max() <= 100
    by_pair = dict(zip(zip(src.tolist(), dst.tolist()), w.tolist()))
    assert all(by_pair[(d, s)] == x for (s, d), x in by_pair.items())


def test_graph_is_fixed_by_its_parameters(graph):
    again = graph500.make_graph(RMAT1, 10)
    assert all(np.array_equal(a, b) for a, b in zip(graph[1:], again[1:]))
    other = graph500.make_graph(dict(RMAT1, graph_seed=1), 10)
    assert not np.array_equal(graph[1], other[1])


def test_cached_graph_generates_once(tmp_path):
    first = graph500.cached_graph(RMAT1, 8, tmp_path)
    second = graph500.cached_graph(RMAT1, 8, tmp_path)
    assert first[-1] is True and second[-1] is False
    assert all(np.array_equal(a, b) for a, b in zip(first[1:4], second[1:4]))
    assert len(list(tmp_path.glob("*.npz"))) == 1


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40, -3])
def test_search_keys_are_every_vertex_with_an_edge_once(graph, seed):
    n, src, _, _ = graph
    keys = graph500.search_keys(src, n, seed)
    deg = np.bincount(src, minlength=n)
    assert np.all(deg[keys] > 0)
    assert sorted(keys.tolist()) == np.flatnonzero(deg > 0).tolist()
    assert np.array_equal(keys, graph500.search_keys(src, n, seed))


def test_search_key_order_follows_the_seed(graph):
    n, src, _, _ = graph
    a = graph500.search_keys(src, n, 1)
    b = graph500.search_keys(src, n, 2)
    assert not np.array_equal(a[:16], b[:16])


