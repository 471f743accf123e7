"""Distributed graph execution — partitioning + replica-coherence mirrors.

The paper's data manager adjusts partitions and replicas from access
patterns. TPU adaptation (DESIGN.md §2): partitions are SPMD shards over the
``data`` mesh axis (``shard_map``), and "replicas" become either
  * **all-gather mode** — every partition replicates all vertex values per
    superstep (maximal replication: cheapest compute, highest traffic), or
  * **scatter mode** — edge-to-src-partition placement with per-partition
    partial aggregates merged by ``psum_scatter`` (no replication), or
  * **hub-mirror mode** — the replica-coherence policy: only high-degree
    ("hub") vertex values are mirrored everywhere (Trinity's hub buffering /
    PowerGraph vertex-cut insight); the tail uses the scatter path.

Access statistics that drive the hub set are exactly the out-degrees (how
often a vertex's value is read by other partitions), i.e. the paper's
"predictive model of the data access pattern".

``comm_model()`` reports the per-superstep bytes each mode moves so the
benchmark (and tests) can verify the policy's decision analytically — on the
1-CPU container the collectives run but don't cross real links.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.graph.dyngraph import JoinView


@dataclasses.dataclass
class PartitionedGraph:
    n: int                      # padded global vertex count (divisible by P)
    n_parts: int
    # edges grouped by SOURCE partition, padded to uniform length
    src: jnp.ndarray            # (P, m_pad) global src ids
    dst: jnp.ndarray            # (P, m_pad) global dst ids
    mask: jnp.ndarray           # (P, m_pad) validity
    out_degree: jnp.ndarray     # (n,)
    hubs: jnp.ndarray           # (k,) global ids of mirrored hub vertices
    is_hub: jnp.ndarray         # (n,) bool
    # "src": contiguous src-range placement (edge values local at scatter
    # time — all modes valid). "dst_hash": pre-sharded by destination hash
    # (the ShardedDynamicGraph layout — allgather mode only).
    placement: str = "src"

    @property
    def n_local(self) -> int:
        return self.n // self.n_parts


def partition_graph(view: JoinView, n_parts: int, *, hub_k: int = 0,
                    pad_to: int | None = None) -> PartitionedGraph:
    """Contiguous-range vertex partitioning; edges placed at their source's
    partition (values are local at scatter time)."""
    n = ((view.n + n_parts - 1) // n_parts) * n_parts
    n_local = n // n_parts
    src = np.asarray(view.src)
    dst = np.asarray(view.dst)
    part_of = src // n_local
    m_pad = pad_to or max(1, int(np.bincount(part_of, minlength=n_parts).max()))
    ps = np.zeros((n_parts, m_pad), np.int32)
    pd = np.zeros((n_parts, m_pad), np.int32)
    pm = np.zeros((n_parts, m_pad), bool)
    for p in range(n_parts):
        idx = np.flatnonzero(part_of == p)[:m_pad]
        ps[p, :len(idx)] = src[idx]
        pd[p, :len(idx)] = dst[idx]
        pm[p, :len(idx)] = True
    deg = np.zeros(n, np.float32)
    deg[:view.n] = np.asarray(view.out_degree)
    hubs = np.argsort(-deg)[:hub_k].astype(np.int32) if hub_k else \
        np.zeros(0, np.int32)
    is_hub = np.zeros(n, bool)
    is_hub[hubs] = True
    return PartitionedGraph(n, n_parts, jnp.asarray(ps), jnp.asarray(pd),
                            jnp.asarray(pm), jnp.asarray(deg),
                            jnp.asarray(hubs), jnp.asarray(is_hub))


def partition_graph_sharded(shard_views, *, hub_k: int = 0,
                            pad_to: int | None = None,
                            placement: str = "dst_hash") -> PartitionedGraph:
    """Build a PartitionedGraph from pre-sharded per-shard join views
    (``ShardedDynamicGraph.shard_views``).

    ``placement="dst_hash"`` (default) is the zero-copy fast path: each
    shard's rows ARE its partition's rows, so construction is one padded
    copy per shard — but only the ``allgather`` compute mode is valid
    (partial aggregates merge by ``psum_scatter`` regardless of edge
    placement). ``placement="src"`` re-buckets the concatenated shard
    rows by source range in one vectorized grouping pass (no O(P·m)
    mask-and-gather like ``partition_graph``), making every edge's source
    value local to its partition — which is what unlocks the
    ``scatter``/``hub`` modes of ``distributed_join_group_by``, i.e. lets
    hub-mirror placement compose with the sharded store's views.
    """
    if not shard_views:
        raise ValueError("no shard views")
    if placement not in ("dst_hash", "src"):
        raise ValueError(f"unknown placement {placement!r}")
    n_parts = len(shard_views)
    n = ((shard_views[0].n + n_parts - 1) // n_parts) * n_parts
    deg = np.zeros(n, np.float32)
    for view in shard_views:
        deg[:view.n] += view.np_out_deg
    if placement == "src":
        n_local = n // n_parts
        src = np.concatenate([v.np_src for v in shard_views])
        dst = np.concatenate([v.np_dst for v in shard_views])
        part_of = src // n_local
        order = np.argsort(part_of, kind="stable")
        counts = np.bincount(part_of, minlength=n_parts)
        widest = max(1, int(counts.max()))
        m_pad = pad_to or widest
        if m_pad < widest:
            raise ValueError(
                f"pad_to={m_pad} would silently drop edges (widest "
                f"partition has {widest}); pass pad_to >= {widest}")
        ps = np.zeros((n_parts, m_pad), np.int32)
        pd = np.zeros((n_parts, m_pad), np.int32)
        pm = np.zeros((n_parts, m_pad), bool)
        bounds = np.r_[0, np.cumsum(counts)]
        for p in range(n_parts):
            rows = order[bounds[p]:bounds[p + 1]]
            ps[p, :len(rows)] = src[rows]
            pd[p, :len(rows)] = dst[rows]
            pm[p, :len(rows)] = True
    else:
        widest = max(v.m for v in shard_views)
        m_pad = pad_to or max(1, widest)
        if m_pad < widest:
            raise ValueError(
                f"pad_to={m_pad} would silently drop edges (widest shard "
                f"has {widest}); pass pad_to >= {widest}")
        ps = np.zeros((n_parts, m_pad), np.int32)
        pd = np.zeros((n_parts, m_pad), np.int32)
        pm = np.zeros((n_parts, m_pad), bool)
        for p, view in enumerate(shard_views):
            m = view.m
            ps[p, :m] = view.np_src
            pd[p, :m] = view.np_dst
            pm[p, :m] = True
    hubs = np.argsort(-deg)[:hub_k].astype(np.int32) if hub_k else \
        np.zeros(0, np.int32)
    is_hub = np.zeros(n, bool)
    is_hub[hubs] = True
    return PartitionedGraph(n, n_parts, jnp.asarray(ps), jnp.asarray(pd),
                            jnp.asarray(pm), jnp.asarray(deg),
                            jnp.asarray(hubs), jnp.asarray(is_hub),
                            placement=placement)


def _local_partials(src, dst, mask, values_full, n, exclude_hubs=None):
    contrib = values_full[src] * mask
    if exclude_hubs is not None:
        contrib = contrib * (~exclude_hubs[src])
    return jax.ops.segment_sum(contrib, dst, num_segments=n)


def distributed_join_group_by(pg: PartitionedGraph, values: jnp.ndarray,
                              mesh, *, mode: str = "scatter") -> jnp.ndarray:
    """values: (n,) globally sharded over 'data' as (P, n_local) rows.
    Returns the aggregate, sharded the same way."""
    n, nl = pg.n, pg.n_local
    if pg.placement != "src" and mode in ("scatter", "hub"):
        raise ValueError(
            f"mode {mode!r} needs src-placed edges (local values at scatter "
            f"time); this graph is {pg.placement!r}-placed — use 'allgather'")
    values = values.reshape(pg.n_parts, nl)

    if mode == "allgather":
        def fn(vals_l, src, dst, mask):
            vals = jax.lax.all_gather(vals_l[0], "data", tiled=True)  # (n,)
            part = _local_partials(src[0], dst[0], mask[0], vals, n)
            # edges live at src partitions; results must still merge by dst
            out = jax.lax.psum_scatter(part, "data", tiled=True)
            return out[None]
    elif mode == "scatter":
        def fn(vals_l, src, dst, mask):
            # local values only: every edge's src IS local to this shard
            vals = jnp.zeros((n,), values.dtype)
            idx = jax.lax.axis_index("data")
            vals = jax.lax.dynamic_update_slice(vals, vals_l[0], (idx * nl,))
            part = _local_partials(src[0], dst[0], mask[0], vals, n)
            out = jax.lax.psum_scatter(part, "data", tiled=True)
            return out[None]
    elif mode == "hub":
        def fn(vals_l, src, dst, mask):
            idx = jax.lax.axis_index("data")
            vals = jnp.zeros((n,), values.dtype)
            vals = jax.lax.dynamic_update_slice(vals, vals_l[0], (idx * nl,))
            # mirror ONLY hub values everywhere (small all-gather)
            hub_vals_l = vals_l[0][jnp.clip(pg.hubs - idx * nl, 0, nl - 1)]
            hub_vals_l = hub_vals_l * ((pg.hubs >= idx * nl)
                                       & (pg.hubs < (idx + 1) * nl))
            hub_vals = jax.lax.psum(hub_vals_l, "data")     # (k,) replicated
            vals = vals.at[pg.hubs].set(hub_vals)
            part = _local_partials(src[0], dst[0], mask[0], vals, n)
            out = jax.lax.psum_scatter(part, "data", tiled=True)
            return out[None]
    else:
        raise ValueError(mode)

    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data")),
        out_specs=P("data"))
    out = mapped(values, pg.src, pg.dst, pg.mask)
    return out.reshape(n)


def comm_model(pg: PartitionedGraph, *, bytes_per_value: int = 4) -> dict:
    """Per-superstep bytes moved per device, by mode (ring collectives).
    This is the access-pattern model the replica-coherence policy consults."""
    p = pg.n_parts
    n = pg.n
    k = int(pg.hubs.shape[0])
    ag = (p - 1) / p * n * bytes_per_value          # all-gather values
    ps = (p - 1) / p * n * bytes_per_value          # psum-scatter partials
    return {
        "allgather": ag + ps,
        "scatter": ps,
        "hub": ps + 2 * (p - 1) / p * k * bytes_per_value,
        "n": n, "parts": p, "hubs": k,
    }
