"""``chip_smoke.py`` at a tiny size on the CPU: every phase function and
its checks (the platform check lives in ``main`` only), ``main`` refusing
to run without a TPU, the two swallowed-failure counters of the serving
tier, and the compile-cache rule."""
import os
import pathlib
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

import chip_smoke as cs
from repro.core.versioned import Version
from repro.graph.dyngraph import DynamicGraph, MutationBatch
from repro.graph.query import KHop
from repro.graph.sharded import ShardedDynamicGraph
from repro.kernels import ops
from repro.kernels.segment_sum import MAX_SEGMENTS
from repro.launch import compile_cache
from repro.launch.rpc import GraphRPCClient, GraphRPCServer
from repro.launch.serve_graph import GraphQueryServer

TINY = cs.SmokeSize(vertices=500, load_epochs=3, load_adds=2_000,
                    churn_epochs=2, churn_adds=150, churn_dels=100,
                    shards=4, window=6, windows_per_kind=2)
REPO = pathlib.Path(__file__).resolve().parents[1]


def test_stream_live_set_matches_a_replay():
    batches, src, dst = cs.make_stream(TINY, seed=3)
    assert len(batches) == TINY.load_epochs + TINY.churn_epochs
    assert [b.version.epoch for b in batches] == list(range(len(batches)))
    g = DynamicGraph(TINY.vertices, sum(len(b.add_src) for b in batches))
    for b in batches:
        g.apply(b)
    view = g.join_view(batches[-1].version)
    cs.check_snapshot_edges(view, src, dst)
    assert len(src) == TINY.load_epochs * TINY.load_adds + \
        TINY.churn_epochs * (TINY.churn_adds - TINY.churn_dels)


def test_host_reference_matches_a_loop_oracle():
    rng = np.random.default_rng(1)
    n = 40
    src = rng.integers(0, n, 120, dtype=np.int32)
    dst = rng.integers(0, n, 120, dtype=np.int32)
    host = cs.HostGraph(n, src, dst)
    adj = {u: set() for u in range(n)}
    for u, v in zip(src.tolist(), dst.tolist(), strict=True):
        adj[u].add(v)
    for source in range(n):
        seen, frontier = {source}, {source}
        for hops in range(1, 5):
            frontier = {v for u in frontier for v in adj[u]} - seen
            seen |= frontier
            assert set(np.flatnonzero(host.within_hops(source, hops))) \
                == seen
    pr = host.pagerank()
    assert abs(pr.sum() - 1.0) < 1e-9
    np.testing.assert_array_equal(host.in_degree(),
                                  np.bincount(dst, minlength=n))


def test_smoke_phases_pass_at_tiny_size():
    report = cs.Report()
    cs.smoke(TINY, seed=0, report=report)
    got = report.values
    assert got["answers_checked"] == {
        kind: TINY.window * TINY.windows_per_kind
        for kind in ("k_hop", "reachability", "degree_topk", "pagerank")}
    assert got["prewarm_errors"] == 0 and got["dispatch_errors"] == 0
    assert got["pagerank_kernel_n"] == TINY.vertices


def test_check_answers_rejects_a_wrong_answer():
    batches, src, dst = cs.make_stream(TINY, seed=0)
    host = cs.HostGraph(TINY.vertices, src, dst)
    v = batches[-1].version
    right = host.within_hops(7, 2)
    ok = type("R", (), {"ok": True, "version": v, "value": right})
    assert cs.check_answers([(KHop(7, 2), ok)], host, {v.pack()}) == \
        {"k_hop": 1}
    with pytest.raises(AssertionError, match="unsealed"):
        cs.check_answers([(KHop(7, 2), ok)], host, set())
    bad = type("R", (), {"ok": True, "version": v, "value": ~right})
    with pytest.raises(AssertionError, match="k-hop"):
        cs.check_answers([(KHop(7, 2), bad)], host, {v.pack()})


def test_liveness_kernel_check_covers_dead_rows():
    batches, _, _ = cs.make_stream(TINY, seed=0)
    g = DynamicGraph(TINY.vertices, sum(len(b.add_src) for b in batches))
    for b in batches:
        g.apply(b)
    assert cs.check_liveness_kernel(
        g, [batches[1].version, batches[-1].version]) == g.n_edges
    assert (g.deleted[:g.n_edges] != np.iinfo(np.int32).max).any()


def test_pagerank_kernel_check():
    assert cs.check_pagerank_kernel(64, seed=0) < 1e-6


def test_four_chip_path_on_virtual_devices():
    # four CPU devices exist only in a fresh process
    code = ("import chip_smoke as cs; "
            "d = cs.four_chip_pagerank(cs.SmokeSize(vertices=300, "
            "load_epochs=2, load_adds=1500), 0, cs.Report()); "
            "assert set(d) == {'scatter', 'hub', 'allgather'}")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)]))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_segment_sum_refuses_more_segments_than_its_bound():
    vals = np.ones((8, 1), np.float32)
    segs = np.zeros(8, np.int32)
    with pytest.raises(ValueError, match="MAX_SEGMENTS"):
        ops.segment_sum(vals, segs, MAX_SEGMENTS + 1)


# ------------------------------------------------ swallowed-failure counters
def _sealed_server(**kw):
    sg = ShardedDynamicGraph(2, 50, 400)
    server = GraphQueryServer(sg, **kw)
    rng = np.random.default_rng(0)
    server.step(MutationBatch(
        Version(0, 0), add_src=rng.integers(0, 50, 200, dtype=np.int32),
        add_dst=rng.integers(0, 50, 200, dtype=np.int32)))
    return server


def _wait_for(pred, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not pred():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.01)


def test_prewarm_failure_is_counted(monkeypatch, caplog):
    server = _sealed_server()

    def boom(view, routed):
        raise RuntimeError("prewarm fault")

    monkeypatch.setattr(server.engine, "warm_traces", boom)
    try:
        server.step(MutationBatch(Version(1, 0)))
        _wait_for(lambda: server.stats().prewarm_errors >= 1)
    finally:
        server.stop_prewarm()
    assert "trace prewarm failed" in caplog.text


def test_dispatch_failure_is_counted_and_retried(monkeypatch, caplog):
    server = _sealed_server()
    real = server.run_window
    calls = {"n": 0}
    lock = threading.Lock()

    def flaky(lane=None):
        with lock:
            calls["n"] += 1
            first = calls["n"] == 1
        if first:
            raise ValueError("window fault")
        return real(lane)

    monkeypatch.setattr(server, "run_window", flaky)
    rpc = GraphRPCServer(server).start()
    try:
        with GraphRPCClient(*rpc.address) as cli:
            resp = cli.query(KHop(0, 1), deadline_s=30.0)
    finally:
        rpc.stop()
    assert resp.ok
    assert server.stats().dispatch_errors == 1
    assert "query window failed" in caplog.text


def test_nothing_sealed_is_retried_without_counting():
    sg = ShardedDynamicGraph(2, 50, 400)
    server = GraphQueryServer(sg)
    rpc = GraphRPCServer(server).start()
    try:
        with GraphRPCClient(*rpc.address) as cli:
            cli.send(KHop(0, 1))
            time.sleep(0.1)                 # windows fail: nothing sealed
            server.step(MutationBatch(
                Version(0, 0), add_src=np.array([0], np.int32),
                add_dst=np.array([1], np.int32)))
            resp = cli.recv()
    finally:
        rpc.stop()
    assert resp.ok and bool(resp.value[1])
    assert server.stats().dispatch_errors == 0


# ----------------------------------------------------------- compile cache
@pytest.fixture
def cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_defers_to_the_environment(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
