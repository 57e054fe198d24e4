"""The plain reference the benchmark checks the engine against.

Nothing here imports the program.  Distances come from SciPy's
Dijkstra in float64 over the configuration's edge list: with integer
weights every distance is an integer well inside float32's exact range,
so the engine's float32 answer must equal it exactly.  The reach of a
key (the edges a solve from it relaxes) comes from the connected
components of the same symmetric graph.

``dijkstra_bf16`` is the control: the same shortest-path fixpoint with
every sum rounded to bfloat16, the precision below the configuration's
float32, returned as float32 so that only its values can fail the
comparison.  It exists to show that the comparison fails a
lower-precision answer.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components, dijkstra


class Reference:
    """Reference answers over one graph ``(n, src, dst, w)``, which must
    be symmetric (every configuration's generator symmetrizes)."""

    def __init__(self, n: int, src, dst, w):
        self.n = n
        self.csr = sp.csr_matrix(
            (np.asarray(w, np.float64), (src, dst)), shape=(n, n)
        )
        if self.csr.nnz != len(src):
            raise ValueError("the reference needs one edge per vertex pair")
        self.degree = np.diff(self.csr.indptr)
        _, self.label = connected_components(self.csr, directed=False)
        self.comp_edges = np.bincount(self.label, weights=self.degree)
        self.comp_vertices = np.bincount(self.label)

    def distances(self, key: int) -> np.ndarray:
        """(n,) float64 shortest distances from ``key``; inf = unreached."""
        return dijkstra(self.csr, directed=True, indices=int(key))

    def reach(self, key: int) -> tuple[int, int]:
        """(directed edges, vertices) that a solve from ``key`` reaches:
        those of its connected component."""
        c = self.label[int(key)]
        return int(self.comp_edges[c]), int(self.comp_vertices[c])


def mismatches(answer: np.ndarray, ref: np.ndarray) -> int:
    """Vertices whose answered distance differs from the reference's
    (unreached is inf on both sides); a wrong shape differs everywhere."""
    answer = np.asarray(answer)
    if answer.shape != ref.shape:
        return int(ref.shape[0])
    return int(np.count_nonzero(answer.astype(np.float64) != ref))


def dijkstra_bf16(n: int, src, dst, w, key: int) -> np.ndarray:
    """The control: Bellman-Ford to the fixpoint, each candidate
    ``d[u] + w`` rounded to bfloat16 before the min.  Returns (n,)
    float32 distances, each a bfloat16 value."""
    order = np.argsort(dst, kind="stable")
    s, d = np.asarray(src)[order], np.asarray(dst)[order]
    wt = np.asarray(w, np.float32)[order]
    starts = np.flatnonzero(np.r_[True, d[1:] != d[:-1]])
    targets = d[starts]
    dist = np.full(n, np.inf, np.float32)
    dist[int(key)] = 0.0
    while True:
        cand = (dist[s] + wt).astype(ml_dtypes.bfloat16).astype(np.float32)
        best = np.minimum.reduceat(cand, starts)
        new = dist.copy()
        new[targets] = np.minimum(new[targets], best)
        if np.array_equal(new, dist):
            return dist
        dist = new
