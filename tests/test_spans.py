"""Stage spans and counters: the accumulator, and what the store, the
WAL, the publish and the query engine record into ``ServerStats``."""
import sys
import threading

import numpy as np
import pytest

from repro.core.spans import Spans, span
from repro.graph import compute as gc
from repro.graph.dyngraph import synthesize_churn_stream
from repro.graph import query
from repro.graph.query import KHop, routed_widths
from repro.graph.sharded import ShardedDynamicGraph, replica_route
from repro.launch.serve_graph import GraphQueryServer


def test_span_times_and_counts_into_its_accumulator():
    totals = Spans()
    with span(totals, "a.b", window=3, request=None) as s:
        s.note(rows=5)
    with pytest.raises(KeyError):
        with span(totals, "a.b"):
            raise KeyError("the span still closes")
    with span(None, "a.c"):       # annotation only
        pass
    totals.count("bytes", 7)
    totals.count("bytes", 5)
    seconds, counts, counters = totals.snapshot()
    assert counts == {"a.b": 2} and seconds["a.b"] >= 0.0
    assert counters == {"bytes": 12}


def test_spans_lose_no_update_under_contention():
    totals = Spans()
    threads, per = 8, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with span(totals, "hot"):
                    pass
                totals.count("n", 1)
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    _, counts, counters = totals.snapshot()
    assert counts["hot"] == counters["n"] == threads * per


def _loaded(tmp_path, n=96, shards=3):
    batches = synthesize_churn_stream(n, 3, 80, seed=5, delete_frac=0.1)
    e_max = sum(len(b.add_src) for b in batches) + 16
    store = ShardedDynamicGraph(shards, n, e_max,
                                wal_dir=str(tmp_path / "wal"))
    server = GraphQueryServer(store, auto_reshard=False,
                              prewarm_traces=False)
    for b in batches:
        server.step(b)
    return store, server, len(batches)


def test_the_load_records_store_wal_and_publish(tmp_path):
    store, server, epochs = _loaded(tmp_path)
    try:
        for w in store.wal_shards:
            w.sync()
        s = server.stats()
        for name in ("store.ingest", "store.seal", "serve.publish",
                     "publish.stitch", "publish.replica_plan"):
            assert s.span_n[name] == epochs, name
        assert s.span_n["wal.append"] == epochs * store.n_shards
        assert s.span_n.get("wal.fsync", 0) >= store.n_shards
        # store.apply's time is the store's own per-shard tally
        assert s.span_s["store.apply"] == sum(store.shard_apply_seconds) > 0
        assert "store.apply" not in s.span_n
        # every shard record's bytes, and nothing else, on the counter
        logged = sum(p.stat().st_size for w in store.wal_shards
                     for p in w.segments())
        assert s.wal_bytes == logged > 0
        assert s.served == s.windows == 0 and s.upload_bytes == 0
    finally:
        for w in [*store.wal_shards, store.wal]:
            w.close()


def test_a_routed_window_counts_its_upload_and_its_queue_wait(tmp_path):
    store, server, _ = _loaded(tmp_path)
    try:
        sources = [1, 7, 30]
        for v in sources:
            server.submit(KHop(v, k=2))
        before = server.stats()
        got = server.flush()
        after = server.stats()
        assert len(got) == len(sources)
        _, view, routed = server._serving
        sub_src, _, _, _, _ = replica_route(
            routed.plan, routed.shard_views,
            np.asarray(sources, np.int32), 2)
        rows = gc.pad_pow2(sub_src.size, floor=routed_widths(view.m)[0])
        assert after.upload_bytes - before.upload_bytes == 8 * rows
        for name in ("serve.window", "serve.deliver", "engine.route",
                     "engine.pad", "engine.upload", "engine.fetch"):
            assert after.span_n[name] - before.span_n.get(name, 0) == 1
        assert after.windows - before.windows == 1
        assert after.queue_wait_s - before.queue_wait_s > 0
        # the window's answers are the unrouted view's
        want = np.asarray(gc.batched_k_hop(view, np.asarray(sources), 2))
        for row, r in zip(want, got, strict=True):
            assert np.array_equal(row, r.value)
    finally:
        for w in [*store.wal_shards, store.wal]:
            w.close()


@pytest.mark.parametrize("k", [1, 2, 3])
def test_a_routed_window_counts_its_routed_rows(tmp_path, monkeypatch, k):
    """``routed_rows`` grows by the window's routed rows before the pad,
    and the upload is those rows padded to a power of two (with no
    floor under the pad, which a store this small would reach)."""
    monkeypatch.setattr(query, "MIN_ROUTED_WIDTH", 1)
    store, server, _ = _loaded(tmp_path)
    try:
        sources = [2, 11, 40, 77]
        for v in sources:
            server.submit(KHop(v, k=k))
        before = server.stats()
        server.flush()
        after = server.stats()
        _, view, routed = server._serving
        sub_src, _, _, _, _ = replica_route(
            routed.plan, routed.shard_views,
            np.asarray(sources, np.int32), k)
        routed_rows = after.routed_rows - before.routed_rows
        assert routed_rows == sub_src.size > 0
        assert after.upload_bytes - before.upload_bytes == \
            8 * gc.pad_pow2(routed_rows)
        if k == 1:
            assert routed_rows < view.m
    finally:
        for w in [*store.wal_shards, store.wal]:
            w.close()
