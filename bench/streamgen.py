"""An update stream over a generated graph, drawn on the device from the
seed.

The stream follows Aspen's update experiments (Dhulipala, Blelloch and
Shun, "Low-Latency Graph Streaming Using Compressed Purely-Functional
Trees", PLDI 2019, section 7): batches of rMAT edges are inserted and
deleted while global queries run on snapshots. Here each epoch adds
``pairs_per_epoch`` undirected pairs and deletes as many of the oldest
live pairs, first in, first out, so that the live size stays constant.

Edges are drawn with ``graphgen._kronecker`` under the stream's own
initiator in the base graph's label space, then mapped through the base's
permutation and compaction (``Graph.labels``). A draw is redrawn when an
endpoint has no vertex in the base, when it is a self-loop, or when its
pair is live (or already drawn in the same epoch); ``redrawn_share``
says how many draws that cost.

All pairs ever live form one first-in, first-out queue: the base pairs
first, in an order drawn from the seed, then the stream's pairs in the
order they were drawn. With ``B`` base pairs and ``P`` pairs an epoch,
epoch ``e`` (1, 2, ...; the load is epoch 0) adds the queue's positions
``[B + (e-1)P, B + eP)`` and deletes ``[(e-1)P, eP)``, so the live pairs
of snapshot ``e`` are the positions ``[eP, B + eP)``. Each pair is two
rows, one in each orientation, as in the base.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np

from bench import graphgen

# folded into the seed's key, so that the stream's draws are not the base's
STREAM_TAG = 0x5354


@dataclasses.dataclass(frozen=True)
class Stream:
    """The queue of pairs (``src``/``dst``, int32, the base's first) and
    what an epoch adds and deletes."""
    n: int
    pairs_per_epoch: int
    epochs: int
    base_pairs: int
    src: np.ndarray
    dst: np.ndarray
    drawn: int                  # rMAT draws it took to make the stream

    @property
    def redrawn_share(self) -> float:
        """Share of the draws that were redrawn."""
        made = self.epochs * self.pairs_per_epoch
        return (self.drawn - made) / self.drawn if self.drawn else 0.0

    def rows(self, start: int, stop: int) -> tuple[np.ndarray, np.ndarray]:
        """The rows of the queue's positions ``[start, stop)``: both
        orientations of each pair."""
        s, d = self.src[start:stop], self.dst[start:stop]
        return np.concatenate([s, d]), np.concatenate([d, s])

    def _check(self, epoch: int, first: int) -> None:
        if not first <= epoch <= self.epochs:
            raise ValueError(f"epoch {epoch} is outside the stream's "
                             f"[{first}, {self.epochs}]")

    def adds(self, epoch: int) -> tuple[np.ndarray, np.ndarray]:
        """The rows epoch ``epoch`` adds: both orientations of its pairs."""
        self._check(epoch, 1)
        p = self.pairs_per_epoch
        start = self.base_pairs + (epoch - 1) * p
        return self.rows(start, start + p)

    def deletes(self, epoch: int) -> tuple[np.ndarray, np.ndarray]:
        """The rows epoch ``epoch`` deletes: the oldest live pairs."""
        self._check(epoch, 1)
        p = self.pairs_per_epoch
        return self.rows((epoch - 1) * p, epoch * p)

    def live_pairs(self, epoch: int) -> tuple[np.ndarray, np.ndarray]:
        """The pairs live at snapshot ``epoch`` (0: the base)."""
        self._check(epoch, 0)
        start = epoch * self.pairs_per_epoch
        stop = start + self.base_pairs
        return self.src[start:stop], self.dst[start:stop]

    def live_rows(self, epoch: int) -> tuple[np.ndarray, np.ndarray]:
        """The rows live at snapshot ``epoch``, both orientations."""
        s, d = self.live_pairs(epoch)
        return np.concatenate([s, d]), np.concatenate([d, s])

    def all_adds(self) -> tuple[np.ndarray, np.ndarray]:
        """Every row the stream adds, in all its epochs."""
        return self.rows(self.base_pairs, self.src.size)


@functools.partial(jax.jit, static_argnames=("scale", "count", "a", "b",
                                             "c"))
def _draw(key, labels, *, scale, count, a, b, c):
    """``count`` rMAT edges, as compacted base ids (-1: no vertex)."""
    src, dst = graphgen._kronecker(key, scale, count, a, b, c)
    return labels[src], labels[dst]


@functools.partial(jax.jit, static_argnames=("size",))
def _order(key, *, size):
    return jax.random.permutation(key, size)


def pair_keys(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """One int64 key per undirected pair."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    return np.maximum(src, dst) << 32 | np.minimum(src, dst)


class _LastPosition:
    """The last queue position of each pair key, as sorted runs merged by
    size, so that a lookup costs a binary search per run while the
    stream grows. Positions only grow, so the last is the largest."""

    def __init__(self):
        self.runs: list[tuple[np.ndarray, np.ndarray]] = []

    def add(self, keys: np.ndarray, positions: np.ndarray) -> None:
        while self.runs and self.runs[-1][0].size <= 2 * keys.size:
            k, p = self.runs.pop()
            keys = np.concatenate([k, keys])
            positions = np.concatenate([p, positions])
        order = np.argsort(keys, kind="stable")
        keys, positions = keys[order], positions[order]
        last = np.r_[keys[1:] != keys[:-1], True]
        self.runs.append((keys[last], positions[last]))

    def last(self, keys: np.ndarray) -> np.ndarray:
        """Each key's last position, or -1."""
        out = np.full(keys.size, -1, np.int64)
        for k, p in self.runs:
            i = np.minimum(np.searchsorted(k, keys), k.size - 1)
            hit = k[i] == keys
            out[hit] = np.maximum(out[hit], p[i[hit]])
        return out


def check_config(stream: dict) -> None:
    if stream.get("generator") != "rmat":
        raise ValueError(f"only the rMAT stream is implemented, not {stream}")
    if int(stream["pairs_per_epoch"]) < 1 or int(stream["epochs"]) < 1:
        raise ValueError(f"a stream needs pairs and epochs: {stream}")


def generate(seed: int, graph_cfg: dict, stream: dict,
             graph: graphgen.Graph) -> Stream:
    """The stream that a configuration's ``stream`` section describes,
    over ``graph`` (generated from the same seed and ``graph_cfg``)."""
    check_config(stream)
    p, epochs = int(stream["pairs_per_epoch"]), int(stream["epochs"])
    base = graph.m // 2
    key = jax.random.fold_in(graphgen.seed_key(seed), STREAM_TAG)
    order = np.asarray(jax.device_get(_order(jax.random.fold_in(key, 0),
                                             size=base)))
    fifo_src = np.empty(base + epochs * p, np.int32)
    fifo_dst = np.empty_like(fifo_src)
    fifo_src[:base] = graph.src[:base][order]
    fifo_dst[:base] = graph.dst[:base][order]
    seen = _LastPosition()
    seen.add(pair_keys(fifo_src[:base], fifo_dst[:base]),
             np.arange(base, dtype=np.int64))
    labels = jax.device_put(graph.labels)
    chunk = 1 << max(16, int(np.ceil(np.log2(2 * p * epochs))))
    draws = {"count": chunk, "scale": int(graph_cfg["scale"]),
             "a": float(stream["a"]), "b": float(stream["b"]),
             "c": float(stream["c"])}
    pool_s = pool_d = np.zeros(0, np.int32)
    chunks = 0
    for e in range(1, epochs + 1):
        deleted = (e - 1) * p         # positions below left before epoch e
        width = 2 * p
        while True:
            if pool_s.size < width:
                chunks += 1
                s, d = jax.device_get(_draw(jax.random.fold_in(key, chunks),
                                            labels, **draws))
                pool_s = np.concatenate([pool_s, s])
                pool_d = np.concatenate([pool_d, d])
                continue
            s, d = pool_s[:width], pool_d[:width]
            keys = pair_keys(s, d)
            ok = (s >= 0) & (d >= 0) & (s != d)
            ok &= seen.last(keys) < deleted
            idx = np.flatnonzero(ok)
            _, first = np.unique(keys[idx], return_index=True)
            taken = np.sort(idx[first])
            if taken.size >= p:
                taken = taken[:p]
                break
            if width > 64 * chunk:
                raise ValueError(f"{p} new pairs an epoch cannot be drawn "
                                 f"over {graph.n} vertices: {stream}")
            width *= 2
        at = base + (e - 1) * p
        fifo_src[at:at + p] = s[taken]
        fifo_dst[at:at + p] = d[taken]
        seen.add(keys[taken], np.arange(at, at + p, dtype=np.int64))
        used = int(taken[-1]) + 1
        pool_s, pool_d = pool_s[used:], pool_d[used:]
    drawn = chunks * chunk - pool_s.size
    return Stream(n=graph.n, pairs_per_epoch=p, epochs=epochs,
                  base_pairs=base, src=fifo_src, dst=fifo_dst, drawn=drawn)
