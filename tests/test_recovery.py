"""Durable graph plane: WAL framing, crash recovery, fault injection,
degraded serving, and the retrying RPC client.

The contracts under test, per the "Durability & recovery" section of
``docs/ARCHITECTURE.md``:

* the WAL record framing round-trips payload rows byte-identically, and
  the committed fixture corpus pins the on-disk format: a torn tail is
  truncated with a warning, while mid-segment corruption (bad CRC,
  unframeable length prefix, trailing bytes in a closed segment) is a
  typed :class:`WalCorruptionError` naming segment + byte offset,
* recovery equals replay: a store recovered from checkpoint + WAL tail is
  byte-identical to an uncrashed oracle at EVERY sealed version — across
  shard counts, checkpoint cadences, and split/merge cutovers — and keeps
  ingesting identically afterwards,
* with batched fsync a crash loses only the unsynced suffix: recovery
  lands at the durable frontier, truncates the dead tail, and re-driving
  the lost epochs converges with the oracle,
* checkpoint saves are crash-atomic: an interrupted save (data file or
  manifest) leaves the previous checkpoint fully restorable,
* the serving tier degrades instead of dying: an injected shard fault
  holds the published snapshot, stamps responses ``degraded``, surfaces
  ``stale_epochs``/``seal_failures`` in stats, and catches up after heal,
* the RPC client retries ``ERR_OVERLOADED`` and transport faults with
  capped exponential backoff + jitter, honors the deadline as a total
  budget, surfaces the ORIGINAL typed response on give-up, and never
  retries non-retryable typed errors.
"""
import os
import pathlib
import shutil
import socket
import threading
import time

import numpy as np
import pytest

from repro.core.versioned import Version
from repro.graph import compute as gc
from repro.graph.dyngraph import synthesize_churn_stream
from repro.graph.query import (ERR_BAD_QUERY, ERR_OVERLOADED, KHop,
                               QueryRequest, QueryResponse)
from repro.graph.sharded import ShardedDynamicGraph, encode_payload_rows
from repro.graph.wal import (FaultInjector, GraphCheckpointManager,
                             GraphWal, ShardFaultError, ShardWal,
                             WalCorruptionError, encode_record,
                             rows_to_body, scan_segment,
                             scan_shard_records, truncate_shard_after)
from repro.launch import rpc
from repro.launch.serve_graph import GraphQueryServer

FIXTURES = pathlib.Path(__file__).parent / "wal_fixtures"


# ----------------------------------------------------------- test helpers
def _stream(n, epochs, adds, seed=13):
    batches = synthesize_churn_stream(n, epochs, adds, seed=seed,
                                      delete_frac=0.2)
    e_max = sum(len(b.add_src) for b in batches) * 2 + 64
    return batches, e_max


def _assert_same_view(a, b, ctx=""):
    for field in ("offsets", "src", "dst", "out_degree", "in_degree"):
        got = np.asarray(getattr(a, field))
        want = np.asarray(getattr(b, field))
        assert got.dtype == want.dtype, (ctx, field)
        assert np.array_equal(got, want), (ctx, field)


def _assert_equiv(recovered, oracle, batches, *, check_latest=True):
    """Byte-identical joined views at EVERY sealed version. With
    ``check_latest=False`` the oracle may be ahead (the recovered store
    lost an unsynced suffix it has not re-driven yet)."""
    for b in batches:
        _assert_same_view(recovered.join_view(b.version),
                          oracle.join_view(b.version),
                          ctx=f"epoch {b.version.epoch}")
    if check_latest:
        assert recovered.latest_sealed() == oracle.latest_sealed()


# ------------------------------------------------------------ WAL framing
def test_record_codec_round_trips_byte_identical():
    rng = np.random.default_rng(7)
    for n in (0, 1, 17, 256):
        rows = rng.integers(-(2**31), 2**31 - 1, size=(n, 4),
                            dtype=np.int64).astype(np.int32)
        packed = Version(int(rng.integers(0, 1000)), 0).pack()
        framed = encode_record(packed, rows_to_body(rows))
        path = None
        # scan from bytes via a temp file
        import tempfile
        with tempfile.NamedTemporaryFile(suffix=".wal",
                                         delete=False) as f:
            f.write(framed)
            path = f.name
        try:
            [(got_packed, body, off)], clean = scan_segment(path)
            assert got_packed == packed and off == 0
            assert clean == len(framed)
            got = np.frombuffer(body, "<i4").reshape(-1, 4)
            assert np.array_equal(got, rows)
        finally:
            os.unlink(path)


def test_fixture_corpus_matches_generator(tmp_path):
    """The committed fixtures are exactly what the generator emits — a
    framing change must fail here loudly, never silently re-bless."""
    import sys
    sys.path.insert(0, str(FIXTURES))
    try:
        from make_fixtures import write_fixtures
    finally:
        sys.path.pop(0)
    fresh = write_fixtures(tmp_path)
    assert fresh, "generator produced nothing"
    for name, data in fresh.items():
        committed = (FIXTURES / name).read_bytes()
        assert committed == data, f"fixture {name} drifted from generator"


def test_fixture_interleaved_scans_clean():
    records, clean = scan_segment(FIXTURES / "interleaved.wal")
    assert [Version.unpack(p).epoch for p, _, _ in records] == [0, 1, 2, 3]
    assert records[2][1] == b""                 # empty epoch's record
    assert clean == (FIXTURES / "interleaved.wal").stat().st_size


def test_fixture_torn_tail_truncates_and_warns(tmp_path):
    with pytest.warns(UserWarning, match="torn WAL tail"):
        records, clean = scan_segment(FIXTURES / "torn_tail.wal")
    assert [Version.unpack(p).epoch for p, _, _ in records] == [0, 1]
    assert clean < (FIXTURES / "torn_tail.wal").stat().st_size
    # as a shard segment the torn record simply is not an epoch yet
    d = tmp_path / "shard"
    d.mkdir()
    shutil.copy(FIXTURES / "torn_tail.wal", d / "seg-00000000.wal")
    with pytest.warns(UserWarning, match="torn WAL tail"):
        by_epoch = scan_shard_records(d)
    assert sorted(by_epoch) == [0, 1]


def test_fixture_truncated_prefix_is_tail_only_for_open_segment():
    with pytest.warns(UserWarning, match="dropping 7 bytes"):
        records, clean = scan_segment(FIXTURES / "truncated_prefix.wal")
    assert len(records) == 1 and clean == 96
    # a CLOSED segment (rotation ends on a record boundary) may not carry
    # a tail at all: same bytes, typed corruption
    with pytest.raises(WalCorruptionError, match="trailing bytes"):
        scan_segment(FIXTURES / "truncated_prefix.wal", tail_ok=False)


@pytest.mark.parametrize("name,reason", [
    ("bad_crc.wal", "CRC mismatch"),
    ("bad_length.wal", "length prefix"),
])
def test_fixture_corruption_raises_typed_with_location(name, reason):
    with pytest.raises(WalCorruptionError, match=reason) as ei:
        scan_segment(FIXTURES / name)
    err = ei.value
    assert err.segment.endswith(name)
    assert err.offset == 96                     # after the first record
    assert f"@ byte {err.offset}" in str(err)


def test_shard_wal_rotation_gc_and_truncation(tmp_path):
    w = ShardWal(tmp_path, 0, fsync="never")
    rows = lambda e: np.full((2, 4), e, np.int32)           # noqa: E731
    for e in range(3):
        w.append(e, rows(e))
    w.rotate(3)
    for e in range(3, 6):
        w.append(e, rows(e))
    w.close()
    assert [p.name for p in w.segments()] == ["seg-00000000.wal",
                                              "seg-00000003.wal"]
    assert sorted(scan_shard_records(tmp_path)) == list(range(6))
    # checkpoint landed at epoch 2: the first segment is dead weight
    assert w.drop_segments_below(3) == 1
    assert sorted(scan_shard_records(tmp_path)) == [3, 4, 5]
    # recovery truncates uncommitted records so re-seals append cleanly
    assert truncate_shard_after(tmp_path, 4) == 1
    assert sorted(scan_shard_records(tmp_path)) == [3, 4]
    assert truncate_shard_after(tmp_path, 4) == 0           # idempotent


# --------------------------------------------------- checkpoint atomicity
def _small_store(batches, e_max, n, **kw):
    sg = ShardedDynamicGraph(2, n, e_max, **kw)
    for b in batches:
        sg.apply(b)
    return sg


@pytest.mark.parametrize("victim", ["ckpt_", "MANIFEST.json"])
def test_interrupted_checkpoint_save_keeps_previous(tmp_path, monkeypatch,
                                                    victim):
    """Kill the save at either ``os.replace`` (data file or manifest):
    the previous checkpoint must stay fully loadable either way."""
    n = 64
    batches, e_max = _stream(n, 4, 40)
    sg = _small_store(batches[:2], e_max, n)
    mgr = GraphCheckpointManager(tmp_path, keep=3)
    mgr.save_graph(sg, epoch=1)
    before = GraphCheckpointManager(tmp_path, keep=3).load_graph()
    assert before is not None and before["epoch"] == 1

    for b in batches[2:]:
        sg.apply(b)
    real = os.replace

    def boom(src, dst, *a, **kw):
        if victim in pathlib.Path(dst).name:
            raise OSError("simulated crash mid-save")
        return real(src, dst, *a, **kw)

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError, match="simulated crash"):
        mgr.save_graph(sg, epoch=3)
    monkeypatch.setattr(os, "replace", real)

    fresh = GraphCheckpointManager(tmp_path, keep=3)
    got = fresh.load_graph()
    if victim == "ckpt_":
        # data file never landed: index still serves epoch 1
        assert got["epoch"] == 1
    else:
        # data landed but the manifest did not: the unlisted .npz is
        # invisible (a later save's GC sweeps it) — epoch 1 still serves
        assert got["epoch"] == 1
    for k, arr in got["shards"][0].items():
        assert np.array_equal(arr, before["shards"][0][k]), k


# ------------------------------------------------- crash recovery: oracle
@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("checkpoint_every", [0, 3])
def test_recover_equals_oracle_at_every_version(tmp_path, n_shards,
                                                checkpoint_every):
    n, epochs = 96, 10
    batches, e_max = _stream(n, epochs, 50)
    sg = ShardedDynamicGraph(n_shards, n, e_max, wal_dir=tmp_path,
                             wal_fsync="always",
                             checkpoint_every=checkpoint_every)
    for b in batches[:8]:
        sg.apply(b)
    # crash: the object is abandoned; "always" fsync made every record
    # durable, so recovery must land on the full frontier
    rec = ShardedDynamicGraph.recover(tmp_path)
    assert rec.coordinator.global_frontier == 7

    oracle = ShardedDynamicGraph(n_shards, n, e_max)
    for b in batches[:8]:
        oracle.apply(b)
    _assert_equiv(rec, oracle, batches[:8])

    # the recovered store is a first-class store: keep ingesting
    for b in batches[8:]:
        rec.apply(b)
        oracle.apply(b)
    _assert_equiv(rec, oracle, batches)
    del sg


def test_recover_across_split_and_merge_cutovers(tmp_path):
    n, epochs = 96, 9
    batches, e_max = _stream(n, epochs, 60, seed=5)
    sg = ShardedDynamicGraph(2, n, e_max, wal_dir=tmp_path,
                             wal_fsync="always", checkpoint_every=4)
    oracle = ShardedDynamicGraph(2, n, e_max)
    for b in batches[:3]:
        sg.apply(b)
        oracle.apply(b)
    sg.split_shard(0)
    oracle.split_shard(0)
    for b in batches[3:6]:
        sg.apply(b)
        oracle.apply(b)
    sg.merge_shards(2)
    oracle.merge_shards(2)
    for b in batches[6:]:
        sg.apply(b)
        oracle.apply(b)

    rec = ShardedDynamicGraph.recover(tmp_path)
    assert rec.plan.history == oracle.plan.history
    assert rec.retired == oracle.retired
    assert rec.coordinator.global_frontier == epochs - 1
    _assert_equiv(rec, oracle, batches)
    # per-shard arrays, not just views, must be byte-identical
    for s_rec, s_ora in zip(rec.shards, oracle.shards, strict=True):
        e = s_ora.n_edges
        assert s_rec.n_edges == e
        for f in ("src", "dst", "created", "deleted"):
            assert np.array_equal(getattr(s_rec, f)[:e],
                                  getattr(s_ora, f)[:e]), f
        assert np.array_equal(s_rec.v_created, s_ora.v_created)

    more, _ = _stream(n, epochs + 2, 60, seed=5)
    for b in more[epochs:]:
        rec.apply(b)
        oracle.apply(b)
    _assert_equiv(rec, oracle, more)
    del sg


def test_batch_fsync_crash_recovers_at_durable_frontier(tmp_path):
    """With batched fsync the unsynced suffix dies with the process; the
    durable frontier is still well defined, the dead tail is truncated,
    and re-driving the lost epochs converges with the oracle."""
    n, epochs = 96, 10
    batches, e_max = _stream(n, epochs, 50, seed=3)
    sg = ShardedDynamicGraph(2, n, e_max, wal_dir=tmp_path,
                             wal_fsync="batch", wal_fsync_every=64,
                             checkpoint_every=4)
    for b in batches:
        sg.apply(b)
    # keep `sg` alive: its unflushed python-level buffers must NOT reach
    # disk (a real crash would lose them), which del/GC would flush
    rec = ShardedDynamicGraph.recover(tmp_path)
    frontier = rec.coordinator.global_frontier
    # checkpoints fsync the WAL when they land, so the ladder's last rung
    # bounds the loss; the unsynced suffix may or may not have made it
    assert 7 <= frontier <= epochs - 1

    # a second recovery from the (now truncated) log is a no-op replay
    rec2 = ShardedDynamicGraph.recover(tmp_path)
    assert rec2.coordinator.global_frontier == frontier

    oracle = ShardedDynamicGraph(2, n, e_max)
    for b in batches:
        oracle.apply(b)
    _assert_equiv(rec, oracle, batches[:frontier + 1], check_latest=False)
    for b in batches[frontier + 1:]:            # re-drive the lost tail
        rec.apply(b)
    _assert_equiv(rec, oracle, batches)
    del sg


def test_recover_survives_torn_shard_tail(tmp_path):
    n = 64
    batches, e_max = _stream(n, 6, 40, seed=9)
    sg = ShardedDynamicGraph(2, n, e_max, wal_dir=tmp_path,
                             wal_fsync="always")
    for b in batches:
        sg.apply(b)
    # simulate a mid-append crash: half a record at the end of shard 0
    seg = sorted(GraphWal.shard_dir(tmp_path, 0).glob("seg-*.wal"))[-1]
    with open(seg, "ab") as f:
        f.write(b"\x00\x01\x02\x03\x04\x05\x06")
    with pytest.warns(UserWarning, match="torn WAL tail"):
        rec = ShardedDynamicGraph.recover(tmp_path)
    assert rec.coordinator.global_frontier == 5
    oracle = ShardedDynamicGraph(2, n, e_max)
    for b in batches:
        oracle.apply(b)
    _assert_equiv(rec, oracle, batches)
    del sg


def test_recover_refuses_mid_segment_corruption(tmp_path):
    n = 64
    batches, e_max = _stream(n, 5, 40, seed=9)
    sg = ShardedDynamicGraph(2, n, e_max, wal_dir=tmp_path,
                             wal_fsync="always")
    for b in batches:
        sg.apply(b)
    seg = sorted(GraphWal.shard_dir(tmp_path, 1).glob("seg-*.wal"))[0]
    data = bytearray(seg.read_bytes())
    data[20] ^= 0xFF                            # flip a body byte
    seg.write_bytes(bytes(data))
    with pytest.raises(WalCorruptionError, match="CRC mismatch") as ei:
        ShardedDynamicGraph.recover(tmp_path)
    assert ei.value.segment.endswith(seg.name)
    del sg


def test_payload_reencode_is_replay_stable():
    """The seal's WAL record re-encodes the merged batches; decode of
    that encoding must reproduce the payload fields exactly (this is the
    byte-stability recovery leans on)."""
    from repro.graph.sharded import decode_payloads
    batches, _ = _stream(48, 3, 30, seed=21)
    for b in batches:
        rows = encode_payload_rows(b)
        # a WAL record's body is exactly these rows; decode + re-encode
        # must be the identity on the byte-stable row form
        [back] = decode_payloads([rows])
        assert np.array_equal(encode_payload_rows(back), rows)


# -------------------------------------------------------- degraded serving
def _served_store(n=128, epochs=3, adds=60, **kw):
    batches, e_max = _stream(n, epochs + 4, adds, seed=11)
    inj = FaultInjector()
    sg = ShardedDynamicGraph(2, n, e_max, fault_injector=inj, **kw)
    srv = GraphQueryServer(sg, auto_reshard=False, prewarm_traces=False)
    for b in batches[:epochs]:
        srv.step(b)
    return srv, sg, inj, batches


def test_fault_injector_kills_seal_cleanly_and_reseals():
    srv, sg, inj, batches = _served_store()
    inj.fail(1)                                 # one-shot
    sg.ingest(batches[3])
    with pytest.raises(ShardFaultError, match="shard 1"):
        sg.seal_epoch(3)
    assert inj.faults_fired == 1
    assert sg.coordinator.global_frontier == 2  # frontier held (I6)
    assert sg.seal_epoch(3) == 3                # one-shot: re-seal works


def test_server_degrades_and_catches_up_matching_oracle():
    srv, sg, inj, batches = _served_store()
    n = 128
    healthy = srv.query(KHop(5, k=1))
    assert healthy.version.epoch == 2

    inj.drop(1)
    srv.step(batches[3])                        # absorbed, not raised
    srv.step(batches[4])
    s = srv.stats()
    assert s.degraded and s.seal_failures == 2 and s.stale_epochs == 2
    r = srv.query(KHop(5, k=1))
    assert r.version.epoch == 2                 # last published snapshot
    # the degraded hint rides on every response in the window
    got = {}
    done = threading.Event()
    srv.submit_request(
        QueryRequest(query=KHop(5, k=1), request_id="x"),
        on_done=lambda resp: (got.update(r=resp), done.set()))
    srv.run_window()
    assert done.wait(1.0) and got["r"].degraded

    inj.heal()
    assert srv.reseal() == 4                    # catch-up through backlog
    s = srv.stats()
    assert not s.degraded and s.stale_epochs == 0
    assert s.seal_failures == 2                 # monotone counter
    r = srv.query(KHop(9, k=2))
    assert r.version.epoch == 4
    oracle = ShardedDynamicGraph(2, n, 100_000)
    for b in batches[:5]:
        oracle.apply(b)
    expect = np.asarray(gc.k_hop(oracle.join_view(batches[4].version),
                                 np.array([9]), 2))
    assert np.asarray(r.value).tobytes() == expect.tobytes()


def test_degraded_flag_round_trips_the_wire():
    ok = QueryResponse.answered(1, np.arange(3), Version(2, 0), 0.1,
                                degraded=True)
    frame = rpc.encode_response(ok)
    assert frame["degraded"] is True
    assert rpc.decode_response(frame).degraded
    healthy = QueryResponse.answered(1, np.arange(3), Version(2, 0), 0.1)
    frame = rpc.encode_response(healthy)
    assert "degraded" not in frame              # absent = healthy default
    assert not rpc.decode_response(frame).degraded


# ------------------------------------------------------- RPC retry client
class _ScriptedFront:
    """Raw-socket stand-in for the RPC server that answers each request
    per a fixed script — retry behavior becomes deterministic, no timing
    luck. Actions: ``shed`` (typed overload), ``ok``, ``bad_query``,
    ``drop`` (close the connection without replying)."""

    def __init__(self, script):
        self.script = list(script)
        self.requests = 0
        self.frames: list[dict] = []
        self._sock = socket.create_server(("127.0.0.1", 0))
        self._sock.settimeout(0.2)
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    @property
    def address(self):
        return self._sock.getsockname()[:2]

    def _loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            with conn:
                self._serve(conn)

    def _serve(self, conn):
        while not self._stop.is_set():
            try:
                frame = rpc.read_frame(conn)
            except (ConnectionError, OSError):
                return
            if frame is None:
                return
            act = self.script[self.requests] \
                if self.requests < len(self.script) else "ok"
            self.requests += 1
            self.frames.append(frame)
            rid = frame.get("id", 0)
            if act == "drop":
                return                          # EOF mid-round-trip
            if act == "shed":
                resp = QueryResponse.failed(rid, ERR_OVERLOADED, "shed")
            elif act == "bad_query":
                resp = QueryResponse.failed(rid, ERR_BAD_QUERY, "nope")
            else:
                resp = QueryResponse.answered(rid, np.arange(3),
                                              Version(1, 0), 0.0)
            try:
                conn.sendall(rpc.encode_frame(rpc.encode_response(resp)))
            except OSError:
                return

    def stop(self):
        self._stop.set()
        self._sock.close()
        self._t.join(timeout=2.0)


@pytest.fixture
def scripted():
    fronts = []

    def make(script, **kw):
        front = _ScriptedFront(script)
        kw.setdefault("retry_base_s", 0.002)
        client = rpc.GraphRPCClient(*front.address, **kw)
        fronts.append((front, client))
        return front, client

    yield make
    for front, client in fronts:
        client.close()
        front.stop()


def test_backoff_is_exponential_capped_and_half_jittered(scripted):
    _, c = scripted(["ok"], retry_cap_s=0.5, jitter=lambda: 0.0)
    base = c.retry_base_s
    assert c._backoff(0) == base * 0.5          # jitter floor: b/2
    assert c._backoff(1) == base * 2 * 0.5
    assert c._backoff(30) == 0.5 * 0.5          # capped at retry_cap_s
    c._jitter = lambda: 1.0
    assert c._backoff(0) == base                # jitter ceiling: b
    assert c._backoff(30) == 0.5


def test_overloaded_is_retried_until_success(scripted):
    front, c = scripted(["shed", "shed", "ok"], jitter=lambda: 1.0)
    r = c.query(KHop(0, k=1))
    assert r.ok and front.requests == 3


def test_give_up_returns_the_original_typed_shed(scripted):
    front, c = scripted(["shed"] * 10, max_retries=2, jitter=lambda: 0.0)
    r = c.query(KHop(0, k=1))
    assert not r.ok and r.error.code == ERR_OVERLOADED
    assert front.requests == 3                  # 1 try + 2 retries


def test_deadline_is_a_total_budget_never_slept_past(scripted):
    front, c = scripted(["shed"] * 10, retry_base_s=1.0, max_retries=5,
                        jitter=lambda: 1.0)
    t0 = time.monotonic()
    r = c.query(KHop(0, k=1), deadline_s=0.05)
    elapsed = time.monotonic() - t0
    assert not r.ok and r.error.code == ERR_OVERLOADED
    assert elapsed < 0.5                        # gave up, did not sleep 1s
    assert front.requests == 1
    # each attempt ships the REMAINING budget to the server
    assert front.frames[0]["deadline_s"] <= 0.05


def test_non_retryable_typed_errors_return_immediately(scripted):
    front, c = scripted(["bad_query", "ok"], jitter=lambda: 1.0)
    r = c.query(KHop(0, k=1))
    assert not r.ok and r.error.code == ERR_BAD_QUERY
    assert front.requests == 1


def test_transport_eof_reconnects_and_replays(scripted):
    front, c = scripted(["drop", "ok"], jitter=lambda: 0.0)
    r = c.query(KHop(0, k=1))
    assert r.ok and front.requests == 2         # at-least-once replay


def test_transport_fault_exhaustion_reraises(scripted):
    front, c = scripted(["drop"] * 10, max_retries=1, jitter=lambda: 0.0)
    with pytest.raises((ConnectionError, OSError)):
        c.query(KHop(0, k=1))
    assert front.requests == 2


# ------------------------------------------------------------- chaos soak
@pytest.mark.chaos
def test_chaos_faults_wal_and_recovery_match_oracle(tmp_path):
    """The acceptance chaos run, shrunk to seconds: a WAL-backed server
    absorbs a seeded schedule of one-shot kills and a drop/heal outage
    while ingesting, reseals to catch up, ends byte-identical to the
    oracle — and a post-hoc recovery from its WAL agrees too."""
    n, epochs = 128, 10
    batches, e_max = _stream(n, epochs, 60, seed=17)
    inj = FaultInjector()
    sg = ShardedDynamicGraph(2, n, e_max, wal_dir=tmp_path,
                             wal_fsync="always", checkpoint_every=4,
                             fault_injector=inj)
    srv = GraphQueryServer(sg, auto_reshard=False, prewarm_traces=False)
    rng = np.random.default_rng(17)
    outage_at, heal_at = 4, 6
    for e, b in enumerate(batches):
        if e in (2, 7):
            inj.fail(int(rng.integers(0, 2)))   # one-shot kill
        if e == outage_at:
            inj.drop(1)
        if e == heal_at:
            inj.heal()
            srv.reseal()
        srv.step(b)
        if e < outage_at or e >= heal_at:
            srv.reseal()                        # catch up after one-shots
    srv.reseal()
    assert srv.stats().seal_failures >= 3
    assert not srv.stats().degraded
    assert sg.coordinator.global_frontier == epochs - 1

    oracle = ShardedDynamicGraph(2, n, e_max)
    for b in batches:
        oracle.apply(b)
    _assert_equiv(sg, oracle, batches)
    inj.heal()
    rec = ShardedDynamicGraph.recover(tmp_path)
    _assert_equiv(rec, oracle, batches)


_OFF_DEVICE_AUDIT = """
import sys
import numpy as np
from jax._src import xla_bridge
from repro.graph.dyngraph import synthesize_churn_stream
from repro.graph.sharded import ShardedDynamicGraph
wal_dir = sys.argv[1]
batches = synthesize_churn_stream(300, 5, 200, seed=1, delete_frac=0.2)
e_max = sum(len(b.add_src) for b in batches) + 16
live = ShardedDynamicGraph(2, 300, e_max, wal_dir=wal_dir)
for b in batches:
    live.ingest(b)
    live.seal_epoch(b.version.epoch)
for w in [*live.wal_shards, live.wal]:
    w.sync()
rec = ShardedDynamicGraph.recover(wal_dir)
oracle = ShardedDynamicGraph(2, 300, e_max)
for b in batches:
    oracle.ingest(b)
    oracle.seal_epoch(b.version.epoch)
    got, want = rec.join_view(b.version), oracle.join_view(b.version)
    for field in ("np_offsets", "np_src", "np_dst"):
        assert np.array_equal(getattr(got, field), getattr(want, field))
assert not xla_bridge._backends, sorted(xla_bridge._backends)
"""


def test_recovery_audit_never_touches_the_accelerator(tmp_path):
    # examples/crash_recovery_demo.py recovers and audits in the parent
    # while a serving child owns the chip: ingest, WAL, recovery and the
    # host-CSR audit must never initialize a JAX backend (fresh process,
    # since this one already has)
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__)
                                          .resolve().parents[1] / "src"))
    out = subprocess.run([sys.executable, "-c", _OFF_DEVICE_AUDIT,
                          str(tmp_path)], env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
