"""Smoke run of the served dynamic-graph path on one TPU chip.

Drives the system through the entry points a user calls, at the size of
LDBC Graphalytics' ``graph500-22`` dataset (2,396,657 vertices,
64,155,735 edges; uniform endpoints from a seed — the Kronecker skew is
not modelled here), and checks every answer:

1. a 4-shard ``ShardedDynamicGraph`` with its write-ahead log on is loaded
   through ``GraphQueryServer.step`` over a few epochs, then churned
   (adds and deletes) for a few more;
2. windows of each served kind (2-hop ``KHop``, ``Reachability``,
   ``DegreeTopK``, ``PageRankQuery``; at most 16 queries each) are
   answered through ``GraphRPCServer``/``GraphRPCClient`` over a local
   socket, every thread in this one process;
3. each answer is checked against a plain NumPy computation over the live
   edge list the stream implies (frontier expansion, ``bincount``
   degrees, power iteration), which shares no code with
   ``repro.graph.compute``; the served snapshot itself must hold exactly
   that edge multiset;
4. the WAL is recovered and the recovered snapshot must be byte-identical
   to the live one;
5. the Pallas kernels run natively: the liveness mask on one shard's real
   stamp arrays, and PageRank through ``segment_sum`` at its largest
   compilable vertex count, each against its reference.

Earlier lines report phase times, compile time, window latencies and the
device's peak memory; they are a smoke run's readings, not a benchmark.
The last line is one JSON object: ``{"ok": true, "device": {...}}``.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # only the 4-device PageRank modes

Without a TPU the script exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

# graph500-22 (LDBC Graphalytics data sets): vertices and edges
GRAPH500_22_VERTICES = 2_396_657
GRAPH500_22_EDGES = 64_155_735


@dataclasses.dataclass(frozen=True)
class SmokeSize:
    """Stream and serving shape. The defaults are the chip run; the tests
    shrink every field."""
    vertices: int = GRAPH500_22_VERTICES
    load_epochs: int = 7
    load_adds: int = GRAPH500_22_EDGES // 7     # exact: 7 * 9,165,105
    churn_epochs: int = 2
    churn_adds: int = 500_000
    churn_dels: int = 500_000
    shards: int = 4
    window: int = 16
    windows_per_kind: int = 2


class Report:
    """Collects the smoke's readings and prints each as it arrives."""

    def __init__(self):
        self.values: dict[str, object] = {}

    def put(self, key: str, value) -> None:
        self.values[key] = value
        print(f"smoke {key} = {value}", flush=True)

    @contextlib.contextmanager
    def timed(self, key: str):
        t0 = time.perf_counter()
        yield
        self.put(f"{key}_s", time.perf_counter() - t0)


# ------------------------------------------------------------------ stream
def make_stream(size: SmokeSize, seed: int):
    """Load epochs from ``synthesize_churn_stream`` (uniform), then churn
    epochs that add uniform edges and delete distinct rows of the load.
    Returns ``(batches, live_src, live_dst)``: the live edge multiset after
    the last epoch, derived from the stream alone."""
    from repro.core.versioned import Version
    from repro.graph.dyngraph import MutationBatch, synthesize_churn_stream
    batches = synthesize_churn_stream(size.vertices, size.load_epochs,
                                      size.load_adds, seed=seed)
    load_src = np.concatenate([b.add_src for b in batches])
    load_dst = np.concatenate([b.add_dst for b in batches])
    rng = np.random.default_rng(seed + 1)
    dead = rng.choice(len(load_src), size.churn_epochs * size.churn_dels,
                      replace=False)
    add_src, add_dst = [], []
    for i in range(size.churn_epochs):
        rows = dead[i * size.churn_dels:(i + 1) * size.churn_dels]
        src = rng.integers(0, size.vertices, size.churn_adds, dtype=np.int32)
        dst = rng.integers(0, size.vertices, size.churn_adds, dtype=np.int32)
        add_src.append(src)
        add_dst.append(dst)
        batches.append(MutationBatch(
            Version(size.load_epochs + i, 0), add_src=src, add_dst=dst,
            del_src=load_src[rows], del_dst=load_dst[rows]))
    keep = np.ones(len(load_src), bool)
    keep[dead] = False
    live_src = np.concatenate([load_src[keep], *add_src])
    live_dst = np.concatenate([load_dst[keep], *add_dst])
    return batches, live_src, live_dst


# --------------------------------------------------------------- reference
class HostGraph:
    """Plain NumPy out-adjacency of one snapshot's edge list — the
    reference the served answers are checked against."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        self.n = n
        self.src = src
        self.dst = dst
        order = np.argsort(src, kind="stable")
        self.nbr = dst[order]
        self.off = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=self.off[1:])

    def neighbors(self, frontier: np.ndarray) -> np.ndarray:
        starts = self.off[frontier]
        lens = self.off[frontier + 1] - starts
        first = np.cumsum(lens) - lens
        idx = np.repeat(starts - first, lens) + np.arange(lens.sum())
        return self.nbr[idx]

    def within_hops(self, source: int, hops) -> np.ndarray:
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

    def in_degree(self) -> np.ndarray:
        return np.bincount(self.dst, minlength=self.n)

    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n)

    def pagerank(self, damping: float = 0.85, tol: float = 1e-10,
                 max_iter: int = 200) -> np.ndarray:
        """float64 power iteration; sink mass is spread uniformly."""
        n = self.n
        out_deg = self.out_degree()
        dangling = out_deg == 0
        inv = 1.0 / np.maximum(out_deg, 1)
        pr = np.full(n, 1.0 / n)
        for _ in range(max_iter):
            agg = np.bincount(self.dst, weights=(pr * inv)[self.src],
                              minlength=n)
            new = (1.0 - damping) / n + damping * (agg + pr[dangling].sum() / n)
            done = np.abs(new - pr).sum() < tol
            pr = new
            if done:
                break
        return pr


# ------------------------------------------------------------------- serve
def build_server(size: SmokeSize, batches, wal_dir: str, report: Report):
    """A WAL-backed 4-shard store behind a GraphQueryServer, loaded and
    churned through ``server.step``."""
    from repro.graph.sharded import ShardedDynamicGraph
    from repro.launch.serve_graph import GraphQueryServer
    rows = sum(len(b.add_src) for b in batches)
    e_max = int(rows / size.shards * 1.02) + 1024
    sg = ShardedDynamicGraph(size.shards, size.vertices, e_max,
                             wal_dir=wal_dir, parallel_apply=size.shards)
    server = GraphQueryServer(sg)
    for b in batches:
        with report.timed(f"step_epoch{b.version.epoch}"):
            server.step(b)
    return sg, server


def _window_queries(kind: str, size: SmokeSize, rng, i: int):
    from repro.graph.query import DegreeTopK, KHop, PageRankQuery, Reachability
    n, w = size.vertices, size.window
    if kind == "k_hop":
        return [KHop(int(s), k=2) for s in rng.integers(0, n, w)]
    if kind == "reachability":
        hops = (4, None)[i % 2]
        return [Reachability(int(a), int(b), max_hops=hops)
                for a, b in rng.integers(0, n, (w, 2))]
    if kind == "degree_topk":
        return [DegreeTopK(int(k), direction=d)
                for k, d in zip(rng.choice([1, 8, 64], w),
                                rng.choice(["in", "out"], w), strict=True)]
    return [PageRankQuery(top_k=None if j % 8 == 0 else 16)
            for j in range(w)]


def serve_windows(address, size: SmokeSize, seed: int, report: Report):
    """Send ``windows_per_kind`` windows of each kind over one socket
    client (all requests of a window in flight together). Returns
    ``[(query, response)]``."""
    from repro.launch.rpc import GraphRPCClient
    rng = np.random.default_rng(seed + 2)
    answered = []
    with GraphRPCClient(*address, timeout_s=900.0) as cli:
        for kind in ("k_hop", "reachability", "degree_topk", "pagerank"):
            for i in range(size.windows_per_kind):
                queries = _window_queries(kind, size, rng, i)
                t0 = time.perf_counter()
                ids = {cli.send(q): q for q in queries}
                got = [cli.recv() for _ in queries]
                report.put(f"window_{kind}_{i}_s", time.perf_counter() - t0)
                answered += [(ids[r.request_id], r) for r in got]
    return answered


def check_answers(answered, host: HostGraph, sealed: set) -> dict:
    """Every response is ok, at a sealed version, and equal to the NumPy
    reference. Returns counts per kind."""
    from repro.graph.query import (DegreeTopK, KHop, PageRankQuery,
                                   Reachability, query_kind)
    counts: dict[str, int] = {}
    pr = None
    for q, r in answered:
        if not r.ok:
            raise AssertionError(f"{q} failed: {r.error}")
        if r.version is None or r.version.pack() not in sealed:
            raise AssertionError(f"{q} answered at unsealed {r.version}")
        if isinstance(q, KHop):
            if not np.array_equal(r.value, host.within_hops(q.source, q.k)):
                raise AssertionError(f"{q}: k-hop set differs")
        elif isinstance(q, Reachability):
            want = bool(host.within_hops(q.src, q.max_hops)[q.dst])
            if bool(r.value) != want:
                raise AssertionError(f"{q}: got {r.value}, want {want}")
        elif isinstance(q, DegreeTopK):
            deg = host.in_degree() if q.direction == "in" \
                else host.out_degree()
            ids, vals = r.value
            k = min(q.k, host.n)
            if (len(ids) != k or len(set(ids.tolist())) != k
                    or not np.array_equal(vals, deg[ids])
                    or np.any(np.diff(vals) > 0)
                    or vals[-1] < np.sort(deg)[-k]):
                raise AssertionError(f"{q}: not the top-{k} degrees")
        elif isinstance(q, PageRankQuery):
            if pr is None:
                pr = host.pagerank()
            # the server stops at an L1 step below 1e-6 in float32
            if q.top_k is None:
                if np.abs(r.value - pr).sum() > 1e-4:
                    raise AssertionError(f"{q}: ranks differ")
            else:
                ids, vals = r.value
                if (np.abs(vals - pr[ids]).max() > 1e-6
                        or vals.min() < np.sort(pr)[-q.top_k] - 1e-6):
                    raise AssertionError(f"{q}: not the top ranks")
        kind = query_kind(q)
        counts[kind] = counts.get(kind, 0) + 1
    return counts


def check_snapshot_edges(view, live_src, live_dst) -> None:
    """The served snapshot holds exactly the stream's live edge multiset
    (in canonical (dst, src) order)."""
    want = np.sort((live_dst.astype(np.int64) << 32)
                   | live_src.astype(np.int64))
    if not np.array_equal(view.np_keys, want):
        raise AssertionError("served snapshot differs from the stream's "
                             "live edges")


def check_recovery(sg, wal_dir: str, version) -> None:
    """Recover the WAL into a fresh store; its view at ``version`` must be
    byte-identical to the live store's."""
    from repro.graph.sharded import ShardedDynamicGraph
    for w in [*sg.wal_shards, sg.wal]:
        if w is not None:       # None where no writer is attached
            w.sync()
    rec = ShardedDynamicGraph.recover(wal_dir)
    try:
        got, want = rec.join_view(version), sg.join_view(version)
        for field in ("np_offsets", "np_src", "np_dst"):
            a, b = getattr(got, field), getattr(want, field)
            if a.dtype != b.dtype or a.tobytes() != b.tobytes():
                raise AssertionError(f"recovered {field} differs")
    finally:
        close_store(rec)


def close_store(sg) -> None:
    """Close a WAL-backed store's logs and its apply pool."""
    for w in [*sg.wal_shards, sg.wal]:
        if w is not None:
            w.close()
    sg.shutdown()


# ----------------------------------------------------------------- kernels
def check_liveness_kernel(shard, versions) -> int:
    """``snapshot_mask(use_kernel=True)`` equals the NumPy mask on the
    shard's real stamp arrays, at each of ``versions``. Returns the rows
    checked."""
    for v in versions:
        if not np.array_equal(shard.snapshot_mask(v, use_kernel=True),
                              shard.snapshot_mask(v)):
            raise AssertionError(f"liveness kernel differs at {v}")
    return shard.n_edges


def check_pagerank_kernel(n: int, seed: int, edge_factor: int = 16):
    """PageRank through the Pallas ``segment_sum`` equals the XLA path on
    a uniform graph of ``n`` vertices. Returns the max abs difference."""
    from repro.core.versioned import Version
    from repro.graph import compute as gc
    from repro.graph.dyngraph import DynamicGraph, MutationBatch
    rng = np.random.default_rng(seed + 3)
    m = n * edge_factor
    g = DynamicGraph(n, m)
    g.apply(MutationBatch(Version(0, 0),
                          add_src=rng.integers(0, n, m, dtype=np.int32),
                          add_dst=rng.integers(0, n, m, dtype=np.int32)))
    view = g.join_view(Version(0, 0))
    xla = np.asarray(gc.pagerank(view, max_iter=30).ranks)
    ker = np.asarray(gc.pagerank(view, max_iter=30, use_kernel=True).ranks)
    # float32 sums in two orders over ~edge_factor terms per vertex
    np.testing.assert_allclose(ker, xla, rtol=1e-4, atol=1e-9)
    return float(np.abs(ker - xla).max())


# -------------------------------------------------------------- four chips
def four_chip_pagerank(size: SmokeSize, seed: int, report: Report,
                       supersteps: int = 3) -> dict:
    """A few PageRank supersteps through ``distributed_join_group_by`` in
    each mode on a 4-device mesh over the load snapshot's per-shard views,
    compared with single-device ``join_group_by``. Returns the max abs
    difference per mode."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.graph.compute import join_group_by
    from repro.graph.dyngraph import synthesize_churn_stream
    from repro.graph.partition import (distributed_join_group_by,
                                       partition_graph_sharded)
    from repro.graph.sharded import ShardedDynamicGraph
    batches = synthesize_churn_stream(size.vertices, size.load_epochs,
                                      size.load_adds, seed=seed)
    rows = sum(len(b.add_src) for b in batches)
    with report.timed("four_chip_load"):
        sg = ShardedDynamicGraph(4, size.vertices,
                                 int(rows / 4 * 1.02) + 1024,
                                 parallel_apply=4)
        for b in batches:
            sg.apply(b)
        v = sg.latest_sealed()
        view = sg.join_view(v)
        # mirror the 1024 highest out-degree vertices (fewer on tiny graphs)
        pg = partition_graph_sharded(sg.shard_views(v),
                                     hub_k=min(1024, view.n // 8),
                                     placement="src")
    sg.shutdown()
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    n, damping = view.n, 0.85
    out_deg = jnp.maximum(view.out_degree, 1.0)

    def superstep(pr, aggregate):
        return (1.0 - damping) / n + damping * aggregate(pr / out_deg)

    pr0 = jnp.full((n,), 1.0 / n, jnp.float32)
    want = pr0
    for _ in range(supersteps):
        want = superstep(want, lambda x: join_group_by(view, x))
    want = np.asarray(want)
    diffs = {}
    for mode in ("scatter", "hub", "allgather"):
        def aggregate(x, mode=mode):
            padded = jnp.zeros((pg.n,), x.dtype).at[:n].set(x)
            return distributed_join_group_by(pg, padded, mesh,
                                             mode=mode)[:n]
        with report.timed(f"four_chip_{mode}"):
            got = pr0
            for _ in range(supersteps):
                got = superstep(got, aggregate)
            got = np.asarray(got)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-12)
        diffs[mode] = float(np.abs(got - want).max())
        report.put(f"four_chip_{mode}_max_abs_diff", diffs[mode])
    return diffs


# -------------------------------------------------------------------- main
def smoke(size: SmokeSize, seed: int, report: Report) -> None:
    """The one-chip smoke: every phase, each raising on a wrong answer."""
    import jax
    from repro.kernels.segment_sum import MAX_SEGMENTS
    from repro.launch.rpc import GraphRPCServer
    n_kernel = min(MAX_SEGMENTS, size.vertices)
    with report.timed("pagerank_kernel"):
        diff = check_pagerank_kernel(n_kernel, seed)
    report.put("pagerank_kernel_n", n_kernel)
    report.put("pagerank_kernel_max_abs_diff", diff)
    with report.timed("stream"):
        batches, live_src, live_dst = make_stream(size, seed)
    report.put("live_edges", len(live_src))
    with tempfile.TemporaryDirectory(prefix="chip_smoke_wal_") as wal_dir:
        with report.timed("load_and_churn"):
            sg, server = build_server(size, batches, wal_dir, report)
        version = sg.latest_sealed()
        view = sg.join_view(version)
        report.put("served_version", str(version))
        report.put("vertices", view.n)
        report.put("edges", view.m)
        with report.timed("check_snapshot_edges"):
            check_snapshot_edges(view, live_src, live_dst)
        rpc = GraphRPCServer(server).start()
        try:
            with report.timed("serve"):
                answered = serve_windows(rpc.address, size, seed, report)
        finally:
            rpc.stop()
            server.stop_prewarm()
        with report.timed("check_answers"):
            host = HostGraph(size.vertices, live_src, live_dst)
            counts = check_answers(answered, host, {version.pack()})
        report.put("answers_checked", counts)
        stats = server.stats()
        report.put("server_latency_s", dict(stats.per_kind_latency_s))
        report.put("prewarm_errors", stats.prewarm_errors)
        report.put("dispatch_errors", stats.dispatch_errors)
        if stats.prewarm_errors or stats.dispatch_errors:
            raise AssertionError("the server swallowed errors (see log)")
        with report.timed("liveness_kernel"):
            rows = check_liveness_kernel(
                sg.shards[0], [batches[size.load_epochs // 2].version,
                               version])
        report.put("liveness_kernel_rows", rows)
        with report.timed("recover"):
            check_recovery(sg, wal_dir, version)
        close_store(sg)
    # CPU devices report no memory stats (the tests run this on the CPU)
    stats = jax.devices()[0].memory_stats() or {}
    report.put("peak_bytes_in_use", stats.get("peak_bytes_in_use"))


class _CompileClock:
    """Sums JAX's backend-compile durations and keeps the first one."""

    def __init__(self):
        self.total = 0.0
        self.first = None
        self.count = 0

    def __call__(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration
            self.count += 1
            if self.first is None:
                self.first = duration


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device PageRank modes against "
                         "single-device join_group_by")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} TPU devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"smoke device_kind = {dev.device_kind}; devices = "
          f"{len(jax.devices())}; compile cache = {enable_compile_cache()}",
          flush=True)
    print("smoke: these readings are a smoke run's, not a benchmark's",
          flush=True)
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    report = Report()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_pagerank(SmokeSize(), args.seed, report)
    else:
        smoke(SmokeSize(), args.seed, report)
    report.put("compile_s", clock.total)
    report.put("first_compile_s", clock.first)
    report.put("compiles", clock.count)
    report.put("total_s", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
