"""The plain reference the graph configurations are checked against.

NumPy and SciPy only, on the host, over an explicit edge list; it
shares no code with the system under test and takes nothing the system
made. Answers follow the served semantics: rows are directed out-edges,
k-hop sets include the source, degree top-k orders by degree and then by
the lower vertex id, and PageRank (damping 0.85) spreads the rank of a
vertex with no out-edge evenly over all ``n`` vertices. PageRank is
solved in float64 as a linear system (the graphs are undirected), not
iterated as the server iterates it.

:class:`Snapshots` gives these answers at any epoch of a configuration:
its live rows are rebuilt from the generated base and update stream,
never read from the store.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import scipy.sparse as sp

DAMPING = 0.85
# conjugate gradients stop once the residual is under this share of the
# right-hand side's norm: the ranks are then within about 1e-9 of their
# own size, four orders below any tolerance the check applies
CG_TOL = 1e-12
CG_MAX_ITERATIONS = 400

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


def degree_topk(n: int, src: np.ndarray, dst: np.ndarray, k: int,
                direction: str) -> tuple[np.ndarray, np.ndarray]:
    """(ids, degrees) of the ``k`` vertices of highest in- or out-degree,
    degrees descending, ties to the lower id."""
    if direction not in ("in", "out"):
        raise ValueError(direction)
    deg = np.bincount(dst if direction == "in" else src, minlength=n)
    ids = np.lexsort((np.arange(n), -deg))[:min(k, n)].copy()
    return ids, deg[ids]


def pagerank(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """(n,) float64 PageRank of an undirected graph given as its pairs
    (each ``src[i]``--``dst[i]`` is two rows, one in each orientation):
    the fixed point of ``x = (1 - d)/n + d * (A D^-1 x + dangling(x)/n)``,
    solved on the host.

    A vertex with no row is isolated, so it sends and receives nothing;
    the fixed point is then ``y / sum(y)`` with ``(I - d A D^-1) y = 1``
    (``y = 1`` at isolated vertices), and on the others, with
    ``y = D^(1/2) w``, ``(I - d D^(-1/2) A D^(-1/2)) w = D^(-1/2) 1``: a
    symmetric system whose eigenvalues lie in ``[1 - d, 1 + d]``, which
    conjugate gradients solve in about 50 products with ``A``, where
    power iteration takes about 160."""
    # y[d] += x[s] over both orientations: a symmetric (dst, src) matrix
    rows = np.concatenate([src, dst])
    cols = np.concatenate([dst, src])
    a = sp.csr_matrix((np.ones(rows.size), (cols, rows)), shape=(n, n))
    deg = np.bincount(rows, minlength=n).astype(np.float64)
    del rows, cols
    inv_sqrt = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1.0)), 0.0)

    def product(w):
        return w - DAMPING * inv_sqrt * (a @ (inv_sqrt * w))
    b = inv_sqrt.copy()
    w = np.zeros(n)
    r = b.copy()
    p = r.copy()
    rr = r @ r
    stop = (CG_TOL ** 2) * (b @ b)
    for _ in range(CG_MAX_ITERATIONS):
        if rr <= stop:
            break
        q = product(p)
        step = rr / (p @ q)
        w += step * p
        r -= step * q
        rr, last = r @ r, rr
        p = r + (rr / last) * p
    else:
        raise RuntimeError("the PageRank reference did not converge")
    y = np.where(deg > 0, np.sqrt(deg) * w, 1.0)
    return y / y.sum()


class Snapshots:
    """The reference's answers at each epoch of a generated graph and its
    update stream (``stream=None``: the graph alone, as epoch 0). Each
    epoch's answers are computed once; only the newest epoch's adjacency
    is kept."""

    def __init__(self, graph, stream=None):
        self.graph = graph
        self.stream = stream
        self._host: tuple[int, Optional[HostGraph]] = (-1, None)
        self._degrees: dict = {}
        self._ranks: dict = {}
        self._top: dict = {}

    @property
    def n(self) -> int:
        return self.graph.n

    def has(self, epoch) -> bool:
        """Whether ``epoch`` is a snapshot of the configuration."""
        last = 0 if self.stream is None else self.stream.epochs
        return epoch is not None and 0 <= epoch <= last

    def _base(self, epoch: int) -> None:
        if epoch != 0:
            raise ValueError(f"no epoch {epoch} without a stream")

    def rows(self, epoch: int) -> tuple[np.ndarray, np.ndarray]:
        """The rows live at ``epoch``, both orientations of each pair."""
        if self.stream is None:
            self._base(epoch)
            return self.graph.src, self.graph.dst
        return self.stream.live_rows(epoch)

    def host(self, epoch: int) -> HostGraph:
        if self._host[0] != epoch:
            self._host = (-1, None)
            self._host = (epoch, HostGraph(self.n, *self.rows(epoch)))
        return self._host[1]

    def degree_topk(self, epoch: int, k: int, direction: str):
        key = (epoch, k, direction)
        if key not in self._degrees:
            self._degrees[key] = degree_topk(self.n, *self.rows(epoch), k,
                                             direction)
        return self._degrees[key]

    def pairs(self, epoch: int) -> tuple[np.ndarray, np.ndarray]:
        """The undirected pairs live at ``epoch``, one orientation each."""
        if self.stream is None:
            self._base(epoch)
            half = self.graph.m // 2
            return self.graph.src[:half], self.graph.dst[:half]
        return self.stream.live_pairs(epoch)

    def ranks(self, epoch: int) -> np.ndarray:
        """(n,) float64 ranks at ``epoch``."""
        if epoch not in self._ranks:
            self._ranks[epoch] = pagerank(self.n, *self.pairs(epoch))
        return self._ranks[epoch]

    def top_ranks(self, epoch: int, k: int) -> tuple[np.ndarray, np.ndarray]:
        """(ids, ranks) of the ``k`` highest ranks at ``epoch``, ranks
        descending, ties to the lower id."""
        if (epoch, k) not in self._top:
            ranks = self.ranks(epoch)
            ids = np.argsort(-ranks, kind="stable")[:k].copy()
            self._top[epoch, k] = ids, ranks[ids]
        return self._top[epoch, k]
