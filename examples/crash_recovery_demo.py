"""Crash-recovery walkthrough: kill -9 a serving process, recover, audit.

The durable graph plane's whole claim in one script (the chaos job runs
it in CI):

1. launch ``python -m repro.launch.serve_graph --rpc-port 0 --wal-dir …``
   as a subprocess — a real serving process appending every sealed epoch
   to its write-ahead log and dropping a graph checkpoint every 4 epochs,
2. poll its RPC stats until the stream is several epochs in, then
   ``SIGKILL`` it mid-stream — no atexit, no flush, no goodbye,
3. recover the store in-process from the WAL directory alone and audit
   it byte-identical against an *uncrashed oracle* (the same stream
   replayed into a fresh store) at every epoch up to the durable
   frontier — torn tails are truncated, never guessed at,
4. relaunch the server with ``--recover``: it resumes the stream after
   the durable frontier, drains the remaining epochs, and answers
   queries at the full final version.

The durable frontier is the *minimum* over the control log's commit
records and every shard's intact WAL records, so whatever the kill tore
off the end costs recovery depth, never correctness (``docs/
ARCHITECTURE.md`` "Durability & recovery" has the argument).

The parent process stays off the accelerator: recovery, the oracle and
the audit are host-side NumPy (the audit is a byte comparison of the
views' host CSR arrays, whose device copies are only made on first
use), and the RPC client only decodes frames. On a TPU host the chip
therefore belongs to whichever serving subprocess is running, never to
this script.

    PYTHONPATH=src python examples/crash_recovery_demo.py
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.core.versioned import Version
from repro.graph.dyngraph import synthesize_churn_stream
from repro.graph.query import KHop
from repro.graph.sharded import ShardedDynamicGraph
from repro.launch.rpc import GraphRPCClient

VERTICES = 600
EPOCHS = 10
ADDS = 400
SHARDS = 2
SEED = 7
CKPT_EVERY = 4
KILL_AFTER_EPOCH = 5          # past the epoch-3 checkpoint + WAL sync


def launch(wal_dir: str, *, recover: bool) -> subprocess.Popen:
    env = dict(os.environ, PYTHONPATH="src")
    cmd = [sys.executable, "-m", "repro.launch.serve_graph",
           "--rpc-port", "0", "--wal-dir", wal_dir,
           "--checkpoint-every", str(CKPT_EVERY),
           "--vertices", str(VERTICES), "--epochs", str(EPOCHS),
           "--adds-per-epoch", str(ADDS), "--shards", str(SHARDS),
           "--seed", str(SEED), "--ingest-delay-s", "0.05"]
    if recover:
        cmd.append("--recover")
    return subprocess.Popen(cmd, stdin=subprocess.PIPE,
                            stdout=subprocess.PIPE, text=True, env=env)


def read_until(proc: subprocess.Popen, pattern: str) -> re.Match:
    while True:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(f"server exited before printing "
                               f"{pattern!r}")
        m = re.match(pattern, line)
        if m:
            return m


def serving_epoch(cli: GraphRPCClient) -> int:
    packed = cli.stats()["serving_version"]
    return -1 if packed is None else Version.unpack(packed).epoch


def main() -> None:
    wal_dir = tempfile.mkdtemp(prefix="crash_demo_wal_")

    # 1-2: serve with the WAL on, kill -9 mid-stream -----------------------
    proc = launch(wal_dir, recover=False)
    m = read_until(proc, r"RPC listening on (\S+):(\d+)")
    host, port = m.group(1), int(m.group(2))
    print(f"serving subprocess up at {host}:{port}, WAL in {wal_dir}")
    deadline = time.monotonic() + 30.0
    with GraphRPCClient(host, port) as cli:
        while serving_epoch(cli) < KILL_AFTER_EPOCH:
            if time.monotonic() > deadline:
                raise RuntimeError("stream never reached the kill epoch")
            time.sleep(0.02)
        seen = serving_epoch(cli)
    proc.kill()                                   # SIGKILL: no cleanup
    proc.wait(timeout=30)
    print(f"killed -9 while serving epoch {seen} (of {EPOCHS})")

    # 3: recover from the log alone, audit against an uncrashed oracle ----
    rec = ShardedDynamicGraph.recover(wal_dir)
    frontier = rec.coordinator.global_frontier
    assert CKPT_EVERY - 1 <= frontier < EPOCHS, frontier
    print(f"recovered at durable frontier {frontier} "
          f"(whatever the kill tore off was truncated, not guessed)")

    batches = synthesize_churn_stream(VERTICES, EPOCHS, ADDS, seed=SEED,
                                      delete_frac=0.2)
    e_max = sum(len(b.add_src) for b in batches) + 16
    oracle = ShardedDynamicGraph(SHARDS, VERTICES, e_max)
    audited = 0
    for b in batches[:frontier + 1]:
        oracle.ingest(b)
        oracle.seal_epoch(b.version.epoch)
        got = rec.join_view(b.version)
        want = oracle.join_view(b.version)
        for field in ("np_offsets", "np_src", "np_dst"):
            assert np.array_equal(getattr(got, field),
                                  getattr(want, field)), \
                f"epoch {b.version.epoch}: {field} diverged"
        audited += 1
    print(f"audit: {audited} recovered views byte-identical to the "
          f"uncrashed oracle")
    for w in rec.wal_shards:                      # release the log before
        if w is not None:                         # the relaunch reopens it
            w.close()
    rec.wal.close()

    # 4: relaunch with --recover and drain the rest of the stream ---------
    proc = launch(wal_dir, recover=True)
    try:
        m = read_until(proc, r"recovered at durable frontier (\d+); "
                             r"resuming (\d+) remaining epochs")
        assert int(m.group(1)) == frontier, m.group(1)
        print(f"relaunched: resuming {m.group(2)} epochs after "
              f"frontier {m.group(1)}")
        m = read_until(proc, r"RPC listening on (\S+):(\d+)")
        host, port = m.group(1), int(m.group(2))
        read_until(proc, r"stream drained")
        with GraphRPCClient(host, port) as cli:
            final = serving_epoch(cli)
            assert final == EPOCHS - 1, final
            r = cli.query(KHop(source=0, k=2), deadline_s=30.0)
            assert r.ok and r.version.epoch == EPOCHS - 1
        print(f"resumed server drained the stream and answers at "
              f"epoch {final}")
    finally:
        proc.stdin.close()                        # the shutdown signal
        proc.wait(timeout=30)
    print("OK: kill -9 lost nothing the log had; recovery matched the "
          "oracle and serving resumed")


if __name__ == "__main__":
    main()
