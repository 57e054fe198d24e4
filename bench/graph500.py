"""The benchmark's own copy of the Graph500 Kronecker generator and of
the search-key draw, so that no change to the program's graph code can
move the yardstick.

The generator follows the Graph500 specification (R-MAT with
initiator probabilities ``a, b, c`` and ``d = 1 - a - b - c``, edge
factor 16, vertex labels permuted) with the integer weights of the
paper's RMAT1/RMAT2 graphs.  The edge list is symmetrized, self loops
dropped, and each directed pair kept once at its least weight.
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, weight_max: int, graph_seed: int):
    """Raw directed edges ``(src, dst, weight)`` before symmetrizing:
    one R-MAT quadrant choice per bit level, then a random relabeling
    of the vertices and uniform integer weights in ``[1, weight_max]``."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(graph_seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab, abc = a + b, a + b + c
    for _ in range(scale):
        r = rng.random(m)
        src = (src << 1) | (r >= ab)
        dst = (dst << 1) | ((r >= a) & (r < ab) | (r >= abc))
    perm = rng.permutation(n).astype(np.int32)
    src, dst = perm[src.astype(np.int32)], perm[dst.astype(np.int32)]
    w = rng.integers(1, weight_max + 1, size=m).astype(np.float32)
    return src, dst, w


def symmetric_simple(n: int, src, dst, w):
    """Both directions of every edge, no self loops, one edge per
    directed pair at its least weight; sorted by (src, dst)."""
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    w = np.concatenate([w, w])
    keep = src != dst
    src, dst, w = src[keep], dst[keep], w[keep]
    key = src.astype(np.int64) * np.int64(n) + dst.astype(np.int64)
    order = np.lexsort((w, key))
    key, src, dst, w = key[order], src[order], dst[order], w[order]
    first = np.ones(key.shape[0], dtype=bool)
    first[1:] = key[1:] != key[:-1]
    return src[first], dst[first], w[first]


def make_graph(gen: dict, scale: int):
    """``(n, src, dst, weight)`` of a configuration's graph."""
    if gen["kind"] != "kronecker":
        raise ValueError(f"unknown generator kind {gen['kind']!r}")
    n = 1 << scale
    src, dst, w = kronecker_edges(
        scale, gen["edge_factor"], gen["a"], gen["b"], gen["c"],
        gen["weight_max"], gen["graph_seed"],
    )
    return (n, *symmetric_simple(n, src, dst, w))


def cached_graph(gen: dict, scale: int, cache_dir: Path):
    """``make_graph``, kept as an ``.npz`` under ``cache_dir`` keyed by
    the generator's parameters, so only a checkout's first run of a
    configuration generates it.  Returns ``(n, src, dst, w, generated)``."""
    key = hashlib.sha256(
        json.dumps([gen, scale], sort_keys=True).encode()
    ).hexdigest()[:16]
    path = Path(cache_dir) / f"graph-{key}.npz"
    if path.exists():
        with np.load(path) as z:
            return int(z["n"]), z["src"], z["dst"], z["w"], False
    n, src, dst, w = make_graph(gen, scale)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
    np.savez(tmp, n=n, src=src, dst=dst, w=w)
    os.replace(tmp, path)
    return n, src, dst, w, True


def search_keys(src: np.ndarray, n: int, seed: int) -> np.ndarray:
    """Graph500's search keys: the vertices of degree at least one, in
    an order drawn from ``seed``."""
    has_edge = np.bincount(src, minlength=n) > 0
    rng = np.random.default_rng(seed % (1 << 64))
    return rng.permutation(np.flatnonzero(has_edge))

