"""Replica-plane tests: cold-shard merge coherence, hot-vertex mirror
coherence (invariant I10), replica-first routing byte-identity, and the
src-placement partition path that unlocks scatter/hub modes for
pre-sharded views.

The merge tests mirror ``test_resharding.py``'s split/oracle discipline:
a mid-stream split followed by a merge must leave every sealed snapshot
byte-identical to the loop-based single-store oracle — including
pre-cutover snapshots re-queried afterwards, which must keep resolving
from the retired shard's tombstoned rows. The mirror-coherence test
asserts the I10 rule directly: at every published epoch, the serving
``ReplicaPlan``'s mirror rows are byte-for-byte rows of that epoch's
global view (a mirror can never serve pre-invalidation rows, because it
is rebuilt from the sealed snapshot it serves).

The hypothesis property tests (routing determinism given (plan, ledger);
routed-answer equivalence) self-skip when hypothesis is absent, like
``tests/test_resharding.py``; deterministic variants always run.
"""
import os
import sys
import threading

import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:        # pragma: no cover - exercised in offline envs
    class _StrategyStub:
        """Stands in for hypothesis.strategies at decoration time only."""
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

    def given(*a, **k):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*a, **k):
        return lambda fn: fn

from repro.core.replica import MirrorPlanner, ShardPlanner
from repro.core.versioned import Version
from repro.graph import compute as gc
from repro.graph.dyngraph import synthesize_churn_stream
from repro.graph import query
from repro.graph.query import (KHop, Reachability, RoutedSnapshot,
                               SnapshotQueryEngine, _SubView, routed_widths)
from repro.graph.reference import LoopDynamicGraph
from repro.graph.sharded import (RoutingPlan, ShardedDynamicGraph,
                                 replica_route)
from repro.launch.serve_graph import GraphQueryServer


def _assert_stitched_equal(sg, ref, version):
    view = sg.join_view(version)
    offsets, src, dst, out_deg, in_deg = ref.join_view_arrays(version)
    np.testing.assert_array_equal(np.asarray(view.offsets), offsets)
    np.testing.assert_array_equal(np.asarray(view.src), src)
    np.testing.assert_array_equal(np.asarray(view.dst), dst)
    np.testing.assert_array_equal(view.np_out_deg, out_deg)
    np.testing.assert_array_equal(view.np_in_deg, in_deg)


# ------------------------------------------------- merge/oracle equivalence
@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("delete_frac,readd_frac", [
    (0.0, 0.0),     # add-heavy
    (0.35, 0.4),    # churny: deletes + re-adds cross the migrated range
])
def test_split_then_merge_matches_oracle(n_shards, delete_frac, readd_frac):
    """A mid-stream split followed by a merge of the split pair: stitched
    CSRs stay byte-identical to the single-store oracle at EVERY version
    — pre-split, between the cutovers, and post-merge — and pre-cutover
    snapshots re-queried afterwards keep resolving from the retired
    shard's tombstoned rows."""
    n, epochs, adds = 48, 8, 60
    batches = synthesize_churn_stream(n, epochs, adds, seed=23,
                                      delete_frac=delete_frac,
                                      readd_frac=readd_frac)
    sg = ShardedDynamicGraph(n_shards, n, 8192)
    ref = LoopDynamicGraph(n, 8192)
    for e, b in enumerate(batches):
        sg.apply(b)
        ref.apply(b)
        if e == 2:
            split = sg.split_shard(0)
            assert split["kind"] == "split"
        elif e == 5:
            merge = sg.merge_shards(split["target"])
            assert merge["kind"] == "merge"
            assert merge["target"] == 0
    assert sg.retired == {split["target"]}
    assert sg.n_shards == n_shards + 1          # physical never shrinks
    assert sg.plan.n_shards == n_shards         # live leaves coarsened back
    assert sg.live_shards() == [i for i in range(n_shards + 1)
                                if i != split["target"]]
    for e in range(epochs):
        _assert_stitched_equal(sg, ref, Version(e, 0))
    # the retired shard is fully drained at post-merge snapshots
    assert sg.shard_views(Version(epochs - 1, 0))[split["target"]].m == 0
    # the merged plan routes nothing to the retired shard
    keys = np.random.default_rng(0).integers(0, 1 << 40, 2048)
    assert not (sg.plan.assign(keys) == split["target"]).any()
    # replaying the op-tagged history reproduces the assignment
    np.testing.assert_array_equal(
        RoutingPlan.replay(n_shards, sg.plan.history).assign(keys),
        sg.plan.assign(keys))


def test_split_after_merge_allocates_fresh_shard_id():
    """The plan's physical-allocation counter never reuses a retired id:
    a split after a merge must create the NEXT physical shard, aligned
    with the store's positional lists."""
    n = 32
    batches = synthesize_churn_stream(n, 6, 50, seed=7, delete_frac=0.1)
    sg = ShardedDynamicGraph(2, n, 8192)
    for e, b in enumerate(batches):
        sg.apply(b)
        if e == 1:
            s1 = sg.split_shard(1)       # creates shard 2
        elif e == 3:
            sg.merge_shards(s1["target"])
        elif e == 4:
            s2 = sg.split_shard(0)       # must create shard 3, not reuse 2
    assert (s1["target"], s2["target"]) == (2, 3)
    assert sg.n_shards == 4 and sg.retired == {2}
    assert sg.plan.n_total == 4 and sg.plan.n_shards == 3


def test_merge_requires_split_sibling():
    sg = ShardedDynamicGraph(2, 16, 256)
    sg.apply(synthesize_churn_stream(16, 1, 10, seed=1)[0])
    with pytest.raises(ValueError, match="sibling"):
        sg.merge_shards(0)               # depth-0 base leaf: never merges
    with pytest.raises(ValueError, match="retired|unknown|sibling"):
        sg.merge_shards(5)


def test_merge_rejects_retired_and_double_merge():
    n = 32
    batches = synthesize_churn_stream(n, 5, 40, seed=3)
    sg = ShardedDynamicGraph(2, n, 4096)
    for e, b in enumerate(batches):
        sg.apply(b)
        if e == 1:
            s = sg.split_shard(0)
        elif e == 3:
            sg.merge_shards(s["target"])
    with pytest.raises(ValueError, match="retired"):
        sg.merge_shards(s["target"])
    with pytest.raises(ValueError, match="retired"):
        sg.split_shard(s["target"])


# --------------------------------------------------------- planner policy
def test_planner_proposes_merge_for_cold_siblings():
    p = ShardPlanner(min_load=10.0, min_epochs=2, merge_threshold=0.4)
    pairs = [(0, 2)]
    # pair well below 0.4x mean -> merge
    d = p.propose_merge([5.0, 100.0, 5.0], epochs_observed=3, pairs=pairs)
    assert d is not None and (d.survivor, d.removed) == (0, 2)
    assert "siblings" in d.reason
    # hysteresis: combined load at/above the threshold band -> no merge
    assert p.propose_merge([20.0, 100.0, 20.0], epochs_observed=3,
                           pairs=pairs) is None
    # guards: cooldown, idle store, no legal pairs
    assert p.propose_merge([5.0, 100.0, 5.0], epochs_observed=1,
                           pairs=pairs) is None
    assert p.propose_merge([0.1, 0.5, 0.1], epochs_observed=3,
                           pairs=pairs) is None
    assert p.propose_merge([5.0, 100.0, 5.0], epochs_observed=3,
                           pairs=[]) is None


def test_planner_live_mask_excludes_retired():
    p = ShardPlanner(imbalance_threshold=1.5, min_load=10.0, min_epochs=0)
    # a retired shard's zero load would drag the mean to 50 and make
    # shard 1 look hot; with the mask the two live shards are balanced
    loads = [100.0, 110.0, 0.0]
    live = [True, True, False]
    assert p.propose(loads, epochs_observed=3, live=live) is None
    # and a retired pair never merges
    assert p.propose_merge(loads, epochs_observed=3,
                           pairs=[(0, 2)], live=live) is None


def test_mirror_planner_nomination():
    mp = MirrorPlanner(mirror_k=3, min_heat=2.0)
    heat = np.array([0.0, 5.0, 1.0, 9.0, 5.0, 3.0])
    hot = mp.nominate(heat)
    # top-3 by heat, ties broken toward the lower id, below min_heat cut
    np.testing.assert_array_equal(hot, [1, 3, 4])
    # pure function: identical input -> identical output
    np.testing.assert_array_equal(hot, mp.nominate(heat))
    assert mp.nominate(np.zeros(6)).size == 0
    assert MirrorPlanner(mirror_k=0).nominate(heat).size == 0


# ------------------------------------------- routed execution equivalence
def _routed_store(seed, n=40, n_shards=4, epochs=5):
    batches = synthesize_churn_stream(n, epochs, 60, seed=seed,
                                      delete_frac=0.25, readd_frac=0.3)
    sg = ShardedDynamicGraph(n_shards, n, 8192)
    for b in batches:
        sg.apply(b)
    return sg, sg.latest_sealed()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_replica_route_byte_identical(seed):
    """Frontier kernels on the routed edge subset answer byte-identically
    to the stitched global view, for every mirror-set size from nothing
    (pure locality routing) to everything (pure mirror serving)."""
    sg, v = _routed_store(seed)
    g = sg.join_view(v)
    views = sg.shard_views(v)
    rng = np.random.default_rng(seed)
    for k_hot in (0, 4, 40):
        hot = rng.choice(40, size=k_hot, replace=False) if k_hot else \
            np.zeros(0, np.int64)
        rp = sg.build_replica_plan(v, hot)
        assert rp.n_mirrored == k_hot
        # I10 at rest: mirror rows ARE the snapshot's rows for the
        # mirrored vertices, in canonical order
        sel = rp.mirrored[g.np_src]
        np.testing.assert_array_equal(rp.mirror_src, g.np_src[sel])
        np.testing.assert_array_equal(rp.mirror_dst, g.np_dst[sel])
        anchors = rng.integers(0, 40, 6).astype(np.int32)
        for k in (1, 2, 3):
            sub_src, sub_dst, fanout, hits, misses = replica_route(
                rp, views, anchors, k)
            sub = _SubView(g.n, sub_src, sub_dst)
            np.testing.assert_array_equal(
                np.asarray(gc.batched_k_hop(sub, anchors, k)),
                np.asarray(gc.batched_k_hop(g, anchors, k)))
            assert 0 <= fanout <= len(views)
        # reachability, bounded and unbounded
        srcs = anchors[:3]
        dsts = rng.integers(0, 40, 3).astype(np.int32)
        for hops in (2, None):
            sub_src, sub_dst, *_ = replica_route(rp, views, srcs, hops)
            sub = _SubView(g.n, sub_src, sub_dst)
            np.testing.assert_array_equal(
                np.asarray(gc.batched_reachability(sub, srcs, dsts, hops)),
                np.asarray(gc.batched_reachability(g, srcs, dsts, hops)))
    # all-mirrored anchors with k=1 resolve without touching any shard
    rp = sg.build_replica_plan(v, np.arange(40))
    _, _, fanout, hits, misses = replica_route(
        rp, views, np.array([1, 2, 3]), 1)
    assert fanout == 0 and misses == 0 and hits == 3


def _closure_rows(g, anchors, hops):
    """Brute force on the global view: the (src, dst) rows of every
    vertex within ``hops - 1`` hops of ``anchors`` (every vertex
    reachable from them, for None), sorted."""
    reached = np.zeros(g.n, bool)
    reached[anchors] = True
    frontier, read = reached.copy(), np.zeros(g.n, bool)
    for _ in range(g.n if hops is None else hops):
        read |= frontier
        nxt = np.zeros(g.n, bool)
        nxt[g.np_dst[frontier[g.np_src]]] = True
        frontier = nxt & ~reached
        reached |= nxt
    sel = read[g.np_src]
    return sorted(zip(g.np_src[sel].tolist(), g.np_dst[sel].tolist()))


@pytest.mark.parametrize("k_hot", [0, 4, 40])
@pytest.mark.parametrize("hops", [1, 2, 3, "reach-2", "reach-unbounded"])
def test_replica_route_is_the_frontier_closure(k_hot, hops):
    """The routed rows are exactly the multiset of out-edges of the
    vertices within k - 1 hops of the anchors, whatever part of them the
    mirror serves, and no row of any shard beyond them."""
    hops = {"reach-2": 2, "reach-unbounded": None}.get(hops, hops)
    sg, v = _routed_store(4)
    g = sg.join_view(v)
    views = sg.shard_views(v)
    rng = np.random.default_rng(k_hot)
    hot = rng.choice(40, size=k_hot, replace=False) if k_hot else \
        np.zeros(0, np.int64)
    rp = sg.build_replica_plan(v, hot)
    for anchors in (rng.integers(0, 40, 3), rng.integers(0, 40, 1)):
        sub_src, sub_dst, fanout, hits, misses = replica_route(
            rp, views, anchors.astype(np.int32), hops)
        want = _closure_rows(g, anchors, hops)
        assert sorted(zip(sub_src.tolist(), sub_dst.tolist())) == want
        assert 0 <= fanout <= len(views)
    # one hop from one vertex is a partial closure: fewer rows than the
    # shards hold, though every shard holding one of them is read
    sub_src, _, fanout, _, _ = replica_route(rp, views, [int(g.np_src[0])],
                                             1)
    assert 0 < sub_src.size < sum(sv.m for sv in views)


def test_routed_reachability_with_a_zero_hop_bound_is_unbounded():
    """A hop bound of 0 means unbounded on every path, routed too."""
    sg, v = _routed_store(0)
    g = sg.join_view(v)
    routed = RoutedSnapshot(sg.build_replica_plan(v, np.arange(4)),
                            sg.shard_views(v))
    qs = [Reachability(s, d, max_hops=0)
          for s, d in ((1, 30), (2, 17), (5, 9), (3, 33))]
    got = SnapshotQueryEngine().execute(g, qs, routed=routed)
    assert got == SnapshotQueryEngine().execute(g, qs)
    assert any(got)


def test_routed_windows_of_new_widths_do_not_compile(monkeypatch):
    """After a k-hop signature's first routed window, windows whose
    closures land in other pow2 row widths run without a compile (with
    the floor under the routed width lowered to fit a small store)."""
    from jax import monitoring
    monkeypatch.setattr(query, "MIN_ROUTED_WIDTH", 4)
    sg, v = _routed_store(5, n=64, epochs=8)
    g = sg.join_view(v)
    routed = RoutedSnapshot(sg.build_replica_plan(v, np.zeros(0)),
                            sg.shard_views(v))
    eng = SnapshotQueryEngine(result_cache=False)
    def routed_width(anchors):
        rows = replica_route(routed.plan, routed.shard_views, anchors, 2)[0]
        return gc.pad_pow2(max(rows.size, 1), floor=routed_widths(g.m)[0])
    # windows of two sources, grouped by the width their closure pads to
    by_width = {}
    for a in range(64):
        for b in range(a + 1, 64):
            by_width.setdefault(routed_width([a, b]), [a, b])
    assert len(by_width) >= 3, by_width
    first, *rest = by_width.values()
    eng.execute(g, [KHop(s, k=2) for s in first], routed=routed)
    wants = [np.asarray(gc.batched_k_hop(g, np.asarray(a), 2)) for a in rest]
    compiles = []

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    monitoring.register_event_duration_secs_listener(count)
    try:
        for anchors, want in zip(rest, wants, strict=True):
            got = eng.execute(g, [KHop(s, k=2) for s in anchors],
                              routed=routed)
            np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        monitoring.unregister_event_duration_listener(count)
    assert compiles == []


def test_racing_prepares_compile_each_routed_width_once(monkeypatch):
    """Windows and the publish-time prewarm racing to make a snapshot's
    routed widths ready: each (signature, width) is compiled by exactly
    one of them, and a later window at any width compiles nothing."""
    monkeypatch.setattr(query, "MIN_ROUTED_WIDTH", 4)
    sg, v = _routed_store(6)
    g = sg.join_view(v)
    routed = RoutedSnapshot(sg.build_replica_plan(v, np.zeros(0)),
                            sg.shard_views(v))
    eng = SnapshotQueryEngine()
    sigs = [("k_hop", 2, 4), ("reachability", 4)]
    done = []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        racers = [threading.Thread(target=lambda: done.append(
            eng._prepare_routed(sigs, g, routed)))
            for _ in range((os.cpu_count() or 1) + 2)]
        for t in racers:
            t.start()
        for t in racers:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in racers)
    finally:
        sys.setswitchinterval(old)
    assert len(done) == len(racers)
    assert sum(done) == len(sigs) * len(routed_widths(g.m))
    assert eng._prepare_routed(sigs, g, routed) == 0


def test_engine_routed_execution_and_telemetry():
    """The engine consults the RoutedSnapshot only at its exact version,
    answers byte-identically, and accounts mirror hits / fan-out under
    its own lock."""
    sg, v = _routed_store(3)
    g = sg.join_view(v)
    rp = sg.build_replica_plan(v, np.arange(10))
    routed = RoutedSnapshot(rp, sg.shard_views(v))
    eng, oracle = SnapshotQueryEngine(), SnapshotQueryEngine()
    qs = [KHop(2, k=1), KHop(5, k=1), Reachability(1, 30, max_hops=3)]
    got = eng.execute(g, qs, routed=routed)
    want = oracle.execute(g, qs)
    for a, b in zip(got, want, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rs = eng.replica_stats()
    assert rs["routed_windows"] == 2          # one k-hop + one reach group
    assert rs["mirror_hits"] + rs["mirror_misses"] > 0
    assert sum(rs["fanout_hist"].values()) == 2
    # a version-mismatched RoutedSnapshot is ignored, not misapplied
    older = sg.join_view(Version(0, 0))
    eng2 = SnapshotQueryEngine()
    got2 = eng2.execute(older, [KHop(2, k=1)], routed=routed)
    np.testing.assert_array_equal(
        np.asarray(got2[0]),
        np.asarray(oracle.execute(older, [KHop(2, k=1)])[0]))
    assert eng2.replica_stats()["routed_windows"] == 0


# ---------------------------------------------- I10 across plan churn
def test_mirror_coherence_across_split_and_merge():
    """The satellite's coherence bar: a mid-stream split, then a merge,
    with hot-vertex mirrors refreshing at every publish. At every sealed
    epoch the published plan's mirrors are byte-identical to that
    epoch's global view (never pre-invalidation rows), and every routed
    answer replays byte-identically on a no-replica oracle server."""
    n, epochs = 48, 8
    batches = synthesize_churn_stream(n, epochs, 60, seed=11,
                                      delete_frac=0.3, readd_frac=0.4)
    sg = ShardedDynamicGraph(2, n, 8192)
    srv = GraphQueryServer(sg, auto_reshard=False, mirror_k=16,
                           mirror_min_heat=0.5)
    sg_ref = ShardedDynamicGraph(2, n, 8192)
    srv_ref = GraphQueryServer(sg_ref, replicate_hot=False,
                               auto_reshard=False)
    rng = np.random.default_rng(5)
    hot_pool = rng.integers(0, 12, 6)
    split = None
    for e, b in enumerate(batches):
        srv.step(b)
        srv_ref.step(b)
        if e == 2:
            split = sg.split_shard(0)
        elif e == 5:
            sg.merge_shards(split["target"])
        with srv._serve_lock:
            v, _, routed = srv._serving
        if routed is not None:
            # I10: mirrors at version v == the v snapshot's own rows
            assert routed.plan.version.pack() == v.pack()
            gv = sg.join_view(v)
            sel = routed.plan.mirrored[gv.np_src]
            np.testing.assert_array_equal(routed.plan.mirror_src,
                                          gv.np_src[sel])
            np.testing.assert_array_equal(routed.plan.mirror_dst,
                                          gv.np_dst[sel])
        queries = [KHop(int(hot_pool[i % len(hot_pool)]), k=1 + i % 2)
                   for i in range(6)]
        queries.append(Reachability(int(hot_pool[0]),
                                    int(rng.integers(0, n)), max_hops=4))
        for q in queries:
            got = srv.query(q)
            want = srv_ref.query(q)
            assert got.version.pack() == want.version.pack()
            np.testing.assert_array_equal(np.asarray(got.value),
                                          np.asarray(want.value))
    s = srv.stats()
    assert s.routed_windows > 0
    assert s.split_events == 1 and s.merge_events == 1
    assert 0.0 <= s.mirror_hit_rate <= 1.0
    assert s.mirror_hits > 0                   # the hot pool got mirrored
    assert all(isinstance(k, str) for k in s.fanout_hist)
    assert s.mean_fanout < sg.n_shards         # routing beat full fan-out


# --------------------------------------------------- routing determinism
def _route_fingerprint(sg, v, heat, anchors, mirror_k=8):
    hot = MirrorPlanner(mirror_k=mirror_k, min_heat=0.5).nominate(heat)
    rp = sg.build_replica_plan(v, hot)
    out = replica_route(rp, sg.shard_views(v), anchors, 2)
    return (hot.tobytes(), out[0].tobytes(), out[1].tobytes(), *out[2:])


def test_routing_deterministic_fixed_ledgers():
    """Deterministic variant of the property test (always runs)."""
    sg, v = _routed_store(9)
    rng = np.random.default_rng(9)
    for _ in range(5):
        heat = rng.random(40) * 10
        anchors = rng.integers(0, 40, 5)
        assert _route_fingerprint(sg, v, heat, anchors) == \
            _route_fingerprint(sg, v, heat, anchors)


_PROP_STORE = {}


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(0.0, 100.0), min_size=40, max_size=40),
       st.lists(st.integers(0, 39), min_size=1, max_size=6))
def test_routing_deterministic_property(heat, anchors):
    """Property: replica-first routing is a pure function of (plan,
    ledger) — same heat vector and anchors, same mirrors, same routed
    edge set, same fan-out/hit telemetry."""
    if "sg" not in _PROP_STORE:
        _PROP_STORE["sg"], _PROP_STORE["v"] = _routed_store(13)
    sg, v = _PROP_STORE["sg"], _PROP_STORE["v"]
    heat = np.asarray(heat)
    anchors = np.asarray(anchors, np.int64)
    assert _route_fingerprint(sg, v, heat, anchors) == \
        _route_fingerprint(sg, v, heat, anchors)


# ------------------------------------------ src placement for shard views
def test_partition_sharded_src_placement_unlocks_scatter_and_hub():
    """The satellite's equivalence bar: re-bucketing pre-sharded views by
    source range produces a genuinely src-placed PartitionedGraph —
    scatter and hub modes run (previously rejected) and agree with the
    allgather answer on the dst-hash layout and with the segment-sum
    oracle."""
    import jax
    import jax.numpy as jnp
    from repro.graph.partition import (distributed_join_group_by,
                                       partition_graph_sharded)

    sg, v = _routed_store(21, n=48)
    views = sg.shard_views(v)
    full = sg.join_view(v)
    pg = partition_graph_sharded(views, hub_k=4, placement="src")
    assert pg.placement == "src"
    # every masked edge sits at its source's partition, none dropped
    ps, pm = np.asarray(pg.src), np.asarray(pg.mask)
    n_local = pg.n_local
    for p in range(pg.n_parts):
        assert (ps[p][pm[p]] // n_local == p).all()
    assert int(pm.sum()) == full.m
    # same edge multiset as the store's views
    pd = np.asarray(pg.dst)
    got_edges = np.sort((ps[pm].astype(np.int64) << 32) | pd[pm])
    want_edges = np.sort((full.np_src.astype(np.int64) << 32)
                         | full.np_dst)
    np.testing.assert_array_equal(got_edges, want_edges)

    # compute equivalence on the 1-device mesh: scatter/hub (src
    # placement) == allgather (dst_hash placement) == oracle
    sg1, v1 = _routed_store(21, n=48, n_shards=1)
    full1 = sg1.join_view(v1)
    mesh = jax.make_mesh((1,), ("data",))
    vals = None
    pg_src = partition_graph_sharded(sg1.shard_views(v1), hub_k=4,
                                     placement="src")
    pg_dst = partition_graph_sharded(sg1.shard_views(v1), hub_k=4)
    vals = jnp.arange(pg_src.n, dtype=jnp.float32)
    base = distributed_join_group_by(pg_dst, vals, mesh, mode="allgather")
    oracle = jax.ops.segment_sum(vals[full1.src], full1.dst,
                                 num_segments=pg_src.n)
    np.testing.assert_allclose(np.asarray(base), np.asarray(oracle),
                               rtol=1e-6)
    for mode in ("scatter", "hub"):
        got = distributed_join_group_by(pg_src, vals, mesh, mode=mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   rtol=1e-6)
    # the dst-hash fast path still rejects what it cannot serve
    with pytest.raises(ValueError, match="src-placed"):
        distributed_join_group_by(pg_dst, vals, mesh, mode="scatter")
    with pytest.raises(ValueError, match="placement"):
        partition_graph_sharded(views, placement="bogus")
