"""The plain reference the graph configurations are checked against.

NumPy and SciPy only, over an explicit edge list; it shares no code with
the system under test and takes nothing the system made. Answers follow
the served semantics: rows are directed out-edges, and k-hop sets include
the source.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import scipy.sparse as sp


class HostGraph:
    """Out-adjacency (CSR by source) of one snapshot's edge list."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        self.n = n
        self.src = src
        self.dst = dst
        # coo -> csr is a counting pass, linear in the edge count
        adj = sp.csr_matrix((np.ones(src.size, np.int8), (src, dst)),
                            shape=(n, n))
        self.off = adj.indptr.astype(np.int64)
        self.nbr = adj.indices

    def neighbors(self, frontier: np.ndarray) -> np.ndarray:
        starts = self.off[frontier]
        lens = self.off[frontier + 1] - starts
        first = np.cumsum(lens) - lens
        idx = np.repeat(starts - first, lens) + np.arange(lens.sum())
        return self.nbr[idx]

    def within_hops(self, source: int, hops: Optional[int]) -> np.ndarray:
        """(n,) bool: vertices at most ``hops`` out-hops from ``source``
        (``None``: any number)."""
        reach = np.zeros(self.n, bool)
        reach[source] = True
        frontier = np.asarray([source], np.int64)
        for _ in range(self.n if hops is None else hops):
            if not frontier.size:
                break
            new = np.zeros(self.n, bool)
            new[self.neighbors(frontier)] = True
            new &= ~reach
            reach |= new
            frontier = np.flatnonzero(new)
        return reach

    def out_degree(self) -> np.ndarray:
        return np.diff(self.off)

    def traversed_edges(self, source: int, k: int) -> int:
        """Out-edges any k-hop expansion from ``source`` has to read: those
        of every vertex within ``k - 1`` hops."""
        inner = np.flatnonzero(self.within_hops(source, k - 1))
        return int(self.out_degree()[inner].sum())
