"""The program's stage spans and counters as the benchmark reads them: a
traced RPC window shows every read-path stage, the counters agree with
what the clients saw, and the new per-layer readers read the tiny cell
(and nothing, without raising, from a program that lacks the counters)."""
import json
import threading
import time

import pytest

from bench import harness, tracereduce
from bench.tests import tiny  # noqa: I001 (puts the program on the path)
from repro.graph.dyngraph import synthesize_churn_stream
from repro.graph.query import KHop
from repro.graph.sharded import ShardedDynamicGraph
from repro.launch.rpc import GraphRPCServer
from repro.launch.serve_graph import GraphQueryServer

READ_PATH = ("rpc.batch_wait", "rpc.encode", "rpc.send", "rpc.decode",
             "serve.window", "serve.deliver", "engine.route", "engine.pad",
             "engine.upload", "engine.fetch")
NEW_READERS = ("queue_wait_ms.khop", "route_ms.khop", "upload_bytes.khop",
               "encode_ms.khop", "sent_bytes.khop", "load_s.apply",
               "load_s.wal", "load_s.publish")
BATCH_WAIT_S = 0.05


def settle(server) -> None:
    """Wait for the window in flight to end: a client has its answer
    before the window's delivery (and its span) ends."""
    def closed():
        s = server.stats()
        return s.span_n.get("serve.window", 0) == s.windows
    deadline = time.monotonic() + 30
    while not closed() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert closed()


@pytest.fixture
def served():
    """A 3-shard store behind the RPC front, loaded. The publish-time
    trace prewarm is off: its replays may route on their own thread,
    outside any window."""
    n = 128
    batches = synthesize_churn_stream(n, 2, 120, seed=9, delete_frac=0.0)
    store = ShardedDynamicGraph(3, n, sum(len(b.add_src) for b in batches))
    server = GraphQueryServer(store, auto_reshard=False,
                              prewarm_traces=False)
    for b in batches:
        server.step(b)
    front = GraphRPCServer(server, batch_wait_s=BATCH_WAIT_S).start()
    yield n, server, front
    front.stop()
    store.shutdown()


def test_a_traced_rpc_window_names_every_read_path_stage(served, tmp_path):
    import jax
    n, server, front = served
    clients = [harness.counting_client(front.address, 60) for _ in range(3)]

    def ask(cli, source):
        cli.send(KHop(source, k=2))
        assert cli.recv().ok

    jax.profiler.start_trace(str(tmp_path))
    try:
        for rnd in range(2):
            askers = [threading.Thread(target=ask, args=(c, 11 * i + rnd))
                      for i, c in enumerate(clients)]
            for t in askers:
                t.start()
            for t in askers:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in askers)
        settle(server)          # a span is recorded when it ends
    finally:
        jax.profiler.stop_trace()
        for c in clients:
            c.close()
    host = tracereduce.extract(sorted(tmp_path.rglob("*.xplane.pb"))[-1])[
        "host"]
    names = {name for name, _, _ in host}
    assert set(READ_PATH) <= names, set(READ_PATH) - names
    windows = [(s, s + d) for name, s, d in host if name == "serve.window"]
    routes = [(s, s + d) for name, s, d in host if name == "engine.route"]
    assert routes
    for a, b in routes:
        assert any(lo <= a and b <= hi for lo, hi in windows)


def test_counters_agree_with_what_the_clients_saw(served):
    n, server, front = served
    clients = [harness.counting_client(front.address, 60) for _ in range(3)]
    before = server.stats()
    try:
        # clients take turns, so every request waits the whole batch wait
        for rnd in range(3):
            for i, cli in enumerate(clients):
                assert cli.query(KHop((7 * i + 3 * rnd) % n, k=2)).ok
    finally:
        received = sum(c._sock.received for c in clients)
        for c in clients:
            c.close()
    settle(server)
    after = server.stats()
    windows = after.windows - before.windows
    served_n = after.served - before.served
    assert served_n == 9 and windows >= 1
    assert (after.span_n["serve.window"]
            - before.span_n.get("serve.window", 0)) == windows
    assert after.sent_bytes - before.sent_bytes == received > 0
    assert after.queue_wait_s - before.queue_wait_s >= \
        served_n * BATCH_WAIT_S


def test_the_new_readers_read_the_tiny_cell(monkeypatch, tmp_path):
    root = tiny.tiny_root(tmp_path)
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    assert run.checked.correct
    got = {m: harness.load_metric(root, m)(run) for m in NEW_READERS}
    # the load's stages took time (a WAL-backed load of the whole graph)
    assert all(got[m] > 0 for m in ("load_s.apply", "load_s.wal",
                                    "load_s.publish")), got
    assert got["sent_bytes.khop"] == harness.load_metric(
        root, "answer_bytes.khop")(run)
    assert got["upload_bytes.khop"] > 0 and got["route_ms.khop"] > 0
    assert got["queue_wait_ms.khop"] > 0 and got["encode_ms.khop"] > 0
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in spec["per_layer"]}
    for m in NEW_READERS:
        assert listed[m]["source"] == "program_counter"
        assert listed[m]["workloads"] == [tiny.SNAPSHOT]


def test_the_new_readers_read_nothing_without_the_counters(tmp_path):
    """A program from before the counters (the parent of a comparison)
    reads as no value, not as an error."""
    root = tiny.tiny_root(tmp_path)
    stats = {"windows": 4, "served": 64}
    run = harness.Run(None, 0, 1.0, True, {}, {}, stats_before=stats,
                      stats_after={"windows": 9, "served": 144})
    for m in NEW_READERS:
        assert harness.load_metric(root, m)(run) is None, m
