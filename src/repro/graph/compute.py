"""Graph computing on protocol dataflow — paper §2.3.3.2.

The core primitive is **join-group-by**: join each vertex with its neighbors'
values, group by destination, reduce. With the per-snapshot CSR (*join view*)
this is a segment reduction — ``jax.ops.segment_sum`` portably, the Pallas
``segment_sum`` kernel on TPU.

On top of it: PageRank (offline, full) and **incremental PageRank** (online:
warm-start from the previous snapshot's result — the paper's
"adapt to the graph changes first, then reschedule on the entire graph"),
SSSP with *priority scheduling* (the paper's Dijkstra-via-priority-queue
example), WCC, degree/temporal analytics, and online BFS/k-hop queries, all
usable while mutations stream (snapshot isolation via the versioned store).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.versioned import Version
from repro.graph.dyngraph import DynamicGraph, JoinView


# ----------------------------------------------------------- join-group-by
def join_group_by(view: JoinView, values: jnp.ndarray, *, reduce: str = "sum",
                  use_kernel: bool = False) -> jnp.ndarray:
    """For every vertex d: reduce_{(s,d) in E} values[s].

    values: (n,) or (n, F). Returns same feature shape grouped by dst.
    """
    gathered = values[view.src]
    if use_kernel and reduce == "sum":
        from repro.kernels import ops
        if values.ndim == 1:
            # CSR rows are dst-sorted, so the Pallas sorted-segment-sum
            # applies directly; lift to (m, 1) for the MXU formulation
            return ops.segment_sum(gathered[:, None], view.dst, view.n)[:, 0]
        return ops.segment_sum(gathered, view.dst, view.n)
    if reduce == "sum":
        return jax.ops.segment_sum(gathered, view.dst, num_segments=view.n)
    if reduce == "max":
        return jax.ops.segment_max(gathered, view.dst, num_segments=view.n)
    if reduce == "min":
        return jax.ops.segment_min(gathered, view.dst, num_segments=view.n)
    raise ValueError(reduce)


# ------------------------------------------------------------------ PageRank
@dataclasses.dataclass
class PageRankResult:
    ranks: jnp.ndarray
    iterations: int
    residual: float


def pagerank(view: JoinView, *, damping: float = 0.85, tol: float = 1e-6,
             max_iter: int = 100, init: Optional[jnp.ndarray] = None,
             handle_dangling: bool = True,
             use_kernel: bool = False) -> PageRankResult:
    """Offline PageRank on one snapshot; supports warm start (``init``).
    ``handle_dangling`` redistributes sink mass uniformly (sum(pr)==1)."""
    n = view.n
    out_deg = jnp.maximum(view.out_degree, 1.0)
    dangling = view.out_degree == 0
    pr = jnp.full((n,), 1.0 / n) if init is None else init

    def body(carry):
        pr, _, it = carry
        contrib = pr / out_deg
        agg = join_group_by(view, contrib, use_kernel=use_kernel)
        if handle_dangling:
            # dangling-mass redistribution keeps sum(pr) == 1
            dmass = jnp.sum(jnp.where(dangling, pr, 0.0))
            agg = agg + dmass / n
        new = (1.0 - damping) / n + damping * agg
        resid = jnp.abs(new - pr).sum()
        return new, resid, it + 1

    def cond(carry):
        _, resid, it = carry
        return (resid > tol) & (it < max_iter)

    pr, resid, it = jax.lax.while_loop(
        cond, body, (pr, jnp.asarray(jnp.inf), jnp.asarray(0)))
    return PageRankResult(pr, int(it), float(resid))


def incremental_pagerank(old: PageRankResult, old_view: JoinView,
                         new_view: JoinView, **kw) -> PageRankResult:
    """Online path: warm-start from the previous snapshot's ranks. The
    changed region re-converges locally; unchanged regions are already at
    their fixed point, so iterations drop sharply vs cold start."""
    return pagerank(new_view, init=old.ranks, **kw)


# ---------------------------------------------------------------------- SSSP
@dataclasses.dataclass
class SSSPResult:
    dist: jnp.ndarray
    rounds: int
    relaxations: int


def sssp(view: JoinView, source: int, *, weights: Optional[jnp.ndarray] = None,
         priority_fraction: float = 0.0, max_rounds: int = 10_000) -> SSSPResult:
    """Label-correcting SSSP over in-edges (dst pulls from src).

    ``priority_fraction > 0`` enables the paper's application-specific
    scheduling: only frontier vertices whose tentative distance is within the
    smallest ``priority_fraction`` quantile relax their out-edges each round
    (a vectorized Dijkstra/delta-stepping hybrid). Fewer total relaxations at
    the cost of more rounds — exactly the trade the input scheduler exposes.
    """
    n = view.n
    w = weights if weights is not None else jnp.ones((view.m,), jnp.float32)
    inf = jnp.asarray(jnp.inf, jnp.float32)
    dist0 = jnp.full((n,), jnp.inf, jnp.float32).at[source].set(0.0)
    frontier0 = jnp.zeros((n,), bool).at[source].set(True)

    def body(carry):
        dist, frontier, rounds, relax = carry
        if priority_fraction > 0.0:
            fd = jnp.where(frontier, dist, inf)
            k = jnp.maximum(
                1, jnp.int32(priority_fraction * jnp.sum(frontier)))
            kth = jnp.sort(fd)[jnp.minimum(k - 1, n - 1)]
            active = frontier & (dist <= kth)
        else:
            active = frontier
        # relax in-edges whose src is active
        src_d = dist[view.src]
        src_act = active[view.src]
        cand = jnp.where(src_act, src_d + w, inf)
        best = jax.ops.segment_min(cand, view.dst, num_segments=n)
        improved = best < dist
        dist = jnp.where(improved, best, dist)
        frontier = (frontier & ~active) | improved
        return dist, frontier, rounds + 1, relax + jnp.sum(src_act)

    def cond(carry):
        _, frontier, rounds, _ = carry
        return jnp.any(frontier) & (rounds < max_rounds)

    dist, _, rounds, relax = jax.lax.while_loop(
        cond, body, (dist0, frontier0, jnp.asarray(0), jnp.asarray(0)))
    return SSSPResult(dist, int(rounds), int(relax))


# ----------------------------------------------------------------------- WCC
def wcc(view: JoinView, max_rounds: int = 1000) -> jnp.ndarray:
    """Weakly-connected components by min-label propagation (both directions)."""
    n = view.n
    labels0 = jnp.arange(n)

    def body(carry):
        labels, _, it = carry
        fwd = jax.ops.segment_min(labels[view.src], view.dst, num_segments=n)
        bwd = jax.ops.segment_min(labels[view.dst], view.src, num_segments=n)
        new = jnp.minimum(labels, jnp.minimum(fwd, bwd))
        return new, jnp.any(new != labels), it + 1

    def cond(carry):
        _, changed, it = carry
        return changed & (it < max_rounds)

    labels, _, _ = jax.lax.while_loop(
        cond, body, (labels0, jnp.asarray(True), jnp.asarray(0)))
    return labels


# ------------------------------------------------------------ online queries
def k_hop(view: JoinView, sources: jnp.ndarray, k: int) -> jnp.ndarray:
    """Vertices reachable within k hops (out-direction) — online low-latency
    query; runs on a snapshot while mutations stream."""
    n = view.n
    reach = jnp.zeros((n,), bool).at[sources].set(True)
    for _ in range(k):
        # dst reachable if any in-neighbor src reachable
        hop = jax.ops.segment_max(reach[view.src].astype(jnp.int32),
                                  view.dst, num_segments=n) > 0
        reach = reach | hop
    return reach


def reachability(view: JoinView, src: int, dst: int,
                 max_hops: Optional[int] = None) -> bool:
    n = view.n
    max_hops = max_hops or n
    reach = jnp.zeros((n,), bool).at[src].set(True)
    for _ in range(max_hops):
        hop = jax.ops.segment_max(reach[view.src].astype(jnp.int32),
                                  view.dst, num_segments=n) > 0
        new = reach | hop
        if bool(jnp.all(new == reach)) or bool(new[dst]):
            reach = new
            break
        reach = new
    return bool(reach[dst])


# --------------------------------------------- batched/jitted online queries
# Serving entry points: one jitted call answers a whole window of same-kind
# queries. The compiled sweeps are cached by (padded_m, n, S[, k]) shape:
# query sources are padded to a power-of-two width and the snapshot's edge
# list to a power-of-two length (padding rows target a phantom segment ``n``
# that is sliced off inside the kernel), so consecutive snapshots of a live
# stream and windows of varying size hit the cache instead of recompiling
# per call. ``prepare_*`` compile a shape ahead of time, from its shapes
# alone, so a server can make ready every width its routed windows can take.

def pad_pow2(size: int, floor: int = 1) -> int:
    """Next power of two >= size (>= floor) — the padding rule the serving
    layer uses to keep batched-query shapes (and so jit traces) stable."""
    return max(floor, 1 << max(0, int(size - 1).bit_length()))


def _padded_edges(view: JoinView,
                  pad_edges: bool) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(src, dst) with the edge list padded to a pow2 length; padded rows
    gather vertex 0 (harmless) and scatter into phantom segment ``n``
    (sliced off). Keeps the jitted query trace stable while a live stream
    grows/shrinks m within the bucket. An edge list already at a pow2
    length (a routed subset, padded on the host) passes through as is."""
    m = view.m
    width = pad_pow2(m)
    if not pad_edges or width == m:
        return view.src, view.dst
    src = jnp.zeros((width,), view.src.dtype).at[:m].set(view.src)
    dst = jnp.full((width,), view.n, view.dst.dtype).at[:m].set(view.dst)
    return src, dst


@functools.lru_cache(maxsize=256)
def _compiled(program, shapes: tuple, statics: tuple):
    """``program`` compiled ahead of time for arguments of ``shapes``
    (``(shape, dtype)`` pairs) and the static arguments ``statics``."""
    return program.lower(*(jax.ShapeDtypeStruct(s, d) for s, d in shapes),
                         **dict(statics)).compile()


def _shape(shape, dtype) -> tuple:
    """A :func:`_compiled` key entry: the shape, and the dtype as the
    device array will hold it."""
    return tuple(shape), jnp.dtype(jax.dtypes.canonicalize_dtype(dtype))


def _sweep(program, *args, **statics):
    """Run a sweep program through :func:`_compiled`, so a shape that
    :func:`prepare_k_hop` or :func:`prepare_reachability` compiled ahead
    of time runs without compiling."""
    shapes = tuple(_shape(a.shape, a.dtype) for a in args)
    return _compiled(program, shapes, tuple(sorted(statics.items())))(*args)


@functools.partial(jax.jit, static_argnames=("n", "k"))
def _batched_khop(src, dst, reach0, n, k):
    def step(_, reach):
        # num_segments=n+1: the phantom segment swallows padded edges
        hop = jax.ops.segment_max(reach[src].astype(jnp.int32), dst,
                                  num_segments=n + 1)[:n] > 0
        return reach | hop
    return jax.lax.fori_loop(0, k, step, reach0)


def batched_k_hop(view: JoinView, sources: jnp.ndarray, k: int, *,
                  pad_sources: bool = True,
                  pad_edges: bool = True) -> jnp.ndarray:
    """Per-source k-hop reachability for a whole query window at once.

    Unlike :func:`k_hop` (which unions its sources into ONE frontier), this
    answers S independent queries in a single vectorized sweep: returns
    (S, n) bool, row i = vertices within k out-hops of ``sources[i]``.
    Row i equals ``k_hop(view, sources[i:i+1], k)`` bit for bit.
    """
    sources = jnp.asarray(sources).reshape(-1)
    s = int(sources.shape[0])
    if s == 0:
        return jnp.zeros((0, view.n), bool)
    width = pad_pow2(s) if pad_sources else s
    padded = jnp.zeros((width,), sources.dtype).at[:s].set(sources)
    reach0 = jnp.zeros((view.n, width), bool).at[
        padded, jnp.arange(width)].set(True)
    src, dst = _padded_edges(view, pad_edges)
    reach = _sweep(_batched_khop, src, dst, reach0, n=view.n, k=int(k))
    return reach.T[:s]


def prepare_k_hop(n: int, k: int, sources: int, edges: int,
                  dtype=jnp.int32) -> None:
    """Compile :func:`batched_k_hop`'s sweep ahead of time, with no
    device work: ``n`` vertices, ``k`` hops, a window padded to
    ``sources`` and ``edges`` rows of ``dtype``, already a power of two
    (as the routed subsets are)."""
    edge = _shape((edges,), dtype)
    _compiled(_batched_khop,
              (edge, edge, _shape((n, sources), bool)),
              (("k", int(k)), ("n", int(n))))


@functools.partial(jax.jit, static_argnames=("n",))
def _batched_reach(src, dst, reach0, dst_ids, max_hops, n):
    cols = jnp.arange(dst_ids.shape[0])

    def cond(carry):
        reach, changed, it = carry
        found = jnp.all(reach[dst_ids, cols])
        return changed & ~found & (it < max_hops)

    def body(carry):
        reach, _, it = carry
        hop = jax.ops.segment_max(reach[src].astype(jnp.int32), dst,
                                  num_segments=n + 1)[:n] > 0
        new = reach | hop
        return new, jnp.any(new != reach), it + 1

    reach, _, _ = jax.lax.while_loop(
        cond, body, (reach0, jnp.asarray(True), jnp.asarray(0)))
    return reach[dst_ids, cols]


def batched_reachability(view: JoinView, src_ids: jnp.ndarray,
                         dst_ids: jnp.ndarray,
                         max_hops: Optional[int] = None, *,
                         pad_sources: bool = True,
                         pad_edges: bool = True) -> jnp.ndarray:
    """Multi-source frontier reachability: answers S (src -> dst) queries in
    one frontier sweep — the batched counterpart of :func:`reachability`.
    Returns (S,) bool. The shared frontier stops early once every target is
    found or no per-source frontier changed; ``max_hops`` is a traced
    scalar, so varying it never retraces."""
    src_ids = jnp.asarray(src_ids).reshape(-1)
    dst_ids = jnp.asarray(dst_ids).reshape(-1)
    if src_ids.shape != dst_ids.shape:
        raise ValueError("src_ids and dst_ids must have the same length")
    s = int(src_ids.shape[0])
    if s == 0:
        return jnp.zeros((0,), bool)
    width = pad_pow2(s) if pad_sources else s
    psrc = jnp.zeros((width,), src_ids.dtype).at[:s].set(src_ids)
    pdst = jnp.zeros((width,), dst_ids.dtype).at[:s].set(dst_ids)
    reach0 = jnp.zeros((view.n, width), bool).at[
        psrc, jnp.arange(width)].set(True)
    # falsy max_hops (None or 0) means unbounded — same promotion the
    # scalar reachability() applies, so the two entry points agree
    hops = jnp.asarray(max_hops or view.n, jnp.int32)
    src, dst = _padded_edges(view, pad_edges)
    return _sweep(_batched_reach, src, dst, reach0, pdst, hops,
                  n=view.n)[:s]


def prepare_reachability(n: int, sources: int, edges: int,
                         dtype=jnp.int32) -> None:
    """:func:`prepare_k_hop` for :func:`batched_reachability`'s sweep
    (int32 source and target ids, as the query engine passes them)."""
    edge = _shape((edges,), dtype)
    _compiled(_batched_reach,
              (edge, edge, _shape((n, sources), bool),
               _shape((sources,), jnp.int32), _shape((), jnp.int32)),
              (("n", int(n)),))


@functools.partial(jax.jit, static_argnames=("k",))
def _topk(deg, k):
    return jax.lax.top_k(deg, k)


def degree_topk(view: JoinView, k: int, *,
                direction: str = "in") -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k vertices by in/out-degree on one snapshot — (ids, degrees),
    degrees descending (ties by lowest vertex id, matching a stable sort on
    (-degree, id)). ``k`` larger than n returns all n vertices."""
    if direction not in ("in", "out"):
        raise ValueError(direction)
    deg = view.in_degree if direction == "in" else view.out_degree
    vals, ids = _topk(deg, min(int(k), view.n))
    return ids, vals


# --------------------------------------------------------- temporal analytics
def degree_timeline(g: DynamicGraph, versions: list[Version],
                    use_kernel: bool = False) -> np.ndarray:
    """(T, n) in-degree per snapshot — 'who makes the most friends this
    month?' is an argmax over a diff of this. ``use_kernel`` resolves the
    snapshot masks through the Pallas ``snapshot_resolve`` kernel."""
    out = []
    for v in versions:
        view = g.join_view(v, use_kernel=use_kernel)
        out.append(np.asarray(view.in_degree))
    return np.stack(out)


def pagerank_timeline(g: DynamicGraph, versions: list[Version],
                      incremental: bool = True, use_kernel: bool = False,
                      **kw) -> list[PageRankResult]:
    """PageRank over an evolving sequence of snapshots; incremental mode
    warm-starts each epoch from the previous one (paper stage-4 temporal
    mining). ``use_kernel`` routes both the snapshot resolve and the
    segment reductions through the Pallas kernels."""
    results: list[PageRankResult] = []
    prev: Optional[PageRankResult] = None
    prev_view: Optional[JoinView] = None
    for v in versions:
        view = g.join_view(v, use_kernel=use_kernel)
        if incremental and prev is not None:
            res = incremental_pagerank(prev, prev_view, view,
                                       use_kernel=use_kernel, **kw)
        else:
            res = pagerank(view, use_kernel=use_kernel, **kw)
        results.append(res)
        prev, prev_view = res, view
    return results


def emerging_vertices(g: DynamicGraph, v_old: Version, v_new: Version,
                      top_k: int = 10) -> np.ndarray:
    """Temporal pattern: vertices with the largest in-degree growth between
    two snapshots ('who made the most friends this month?')."""
    d_old = np.asarray(g.join_view(v_old).in_degree)
    d_new = np.asarray(g.join_view(v_new).in_degree)
    growth = d_new - d_old
    return np.argsort(-growth)[:top_k]
