"""The plain reference: triangle counts and LCC of a simple undirected
graph, straight from an edge list.

It shares no code or data with the program under test. Adjacency is
held as one bit set per vertex; the triangles through ``v`` are half of
``sum over neighbours w of |N(v) & N(w)|``, counted by popcount over
the directed edges in blocks. LCC is Eq. (2) of the source paper,
``2 T(v) / (d(v) (d(v) - 1))``, in float64.
"""
from __future__ import annotations

import numpy as np

__all__ = ["Reference"]

_BLOCK = 1 << 15  # directed edges per popcount block (~128 MiB at n=2**15)


class Reference:
    def __init__(self, edges: np.ndarray, n: int):
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        e = e[e[:, 0] != e[:, 1]]
        e = np.concatenate([e, e[:, ::-1]])
        key = np.unique(e[:, 0] * n + e[:, 1])
        self.n = int(n)
        self.src = key // n
        self.dst = key % n
        self.degree = np.bincount(self.src, minlength=n).astype(np.int64)
        words = (n + 63) // 64
        self.bits = np.zeros((n, words), np.uint64)
        np.bitwise_or.at(
            self.bits, (self.src, self.dst >> 6),
            np.left_shift(np.uint64(1), (self.dst & 63).astype(np.uint64)))
        self._tri = None

    @property
    def triangles(self) -> np.ndarray:
        """int64 ``[n]`` triangles through each vertex."""
        if self._tri is None:
            s = np.zeros(self.n, np.int64)
            for lo in range(0, self.src.size, _BLOCK):
                u = self.src[lo:lo + _BLOCK]
                w = self.dst[lo:lo + _BLOCK]
                both = self.bits[u] & self.bits[w]
                np.add.at(s, u, np.bitwise_count(both).sum(1, dtype=np.int64))
            self._tri = s // 2
        return self._tri

    def lcc(self, dtype=np.float64) -> np.ndarray:
        """LCC per vertex, computed in ``dtype`` (float64 as stated;
        the controls ask for less)."""
        t = self.triangles.astype(dtype)
        d = self.degree.astype(dtype)
        two, one = dtype(2.0), dtype(1.0)
        denom = d * (d - one)
        safe = np.where(denom > 0, denom, one).astype(dtype)
        return np.where(denom > 0, (two * t / safe).astype(dtype), dtype(0.0))
