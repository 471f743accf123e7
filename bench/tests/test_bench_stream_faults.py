"""The check refuses a stream run whose timed path is broken underneath,
each fault through its own count: an acknowledged epoch's commit record
dropped, its logged rows altered, its step acknowledged before the
shards' logs were fsynced, a seal aborted for capacity, a step that
applies none or half of its batch, a degree top-k answer from the epoch
before stamped with the newer one, ranks shifted past the tolerance, and
a PageRank top-k answer that leaves out the highest-ranked vertex.
The stale control is refused too. (The exchange between chips is a fault
a one-chip cell cannot have.)"""
import dataclasses

import numpy as np
import pytest

from bench import checks, control, harness
from bench.tests import tiny  # noqa: I001 (puts the program on the path)
from repro.core.versioned import Version
from repro.graph import wal
from repro.graph.query import DegreeTopK, PageRankQuery, SnapshotQueryEngine


@pytest.fixture
def root(tmp_path):
    return tiny.tiny_root(tmp_path)


def run_broken(monkeypatch, root, breaks):
    """One run of the tiny stream cell with ``breaks(dep)`` called as the
    window opens: set-up and warm-up run the program as it is."""
    measure = harness.measure

    def patched(run, dep, clock):
        breaks(dep)
        return measure(run, dep, clock)
    monkeypatch.setattr(harness, "measure", patched)
    return tiny.run_cell(monkeypatch, root, tiny.add_stream_cell(root))


def refused_by(run, number):
    assert not run.checked.correct
    assert run.checked.values[number] > checks.LIMITS[number]


def test_an_acknowledged_epochs_commit_record_dropped(monkeypatch, root):
    def breaks(dep):
        monkeypatch.setattr(wal.GraphWal, "commit_epoch",
                            lambda self, epoch, versions: None)
    run = run_broken(monkeypatch, root, breaks)
    refused_by(run, "wal_missing")
    assert run.checked.values["seal_failed"] == 0


def test_an_acknowledged_epochs_rows_altered(monkeypatch, root):
    append = wal.ShardWal.append

    def altered(self, epoch, rows):
        rows = rows.copy()
        if rows.shape[0]:
            rows[0, 2] = (rows[0, 2] + 1) % 7
        append(self, epoch, rows)

    def breaks(dep):
        monkeypatch.setattr(wal.ShardWal, "append", altered)
    refused_by(run_broken(monkeypatch, root, breaks), "wal_diff")


def test_a_step_acknowledged_before_its_shards_logs_are_fsynced(
        monkeypatch, root):
    def breaks(dep):
        for w in dep.store.wal_shards:
            w.fsync = "never"
    run = run_broken(monkeypatch, root, breaks)
    refused_by(run, "unsynced")
    assert run.checked.values["wal_missing"] == 0


def test_a_seal_aborted_for_capacity(monkeypatch, root):
    def breaks(dep):
        for shard in dep.store.shards:
            shard.e_max = shard.n_edges
    run = run_broken(monkeypatch, root, breaks)
    refused_by(run, "seal_failed")
    assert run.checked.values["unsealed"] == 0     # reads stayed put


def step_with(monkeypatch, rows):
    """Breaks each step as the program takes it: it applies only the
    add and delete rows that ``rows(array)`` keeps."""
    from repro.launch.serve_graph import GraphQueryServer
    step = GraphQueryServer.step

    def broken(self, batch):
        return step(self, dataclasses.replace(
            batch, add_src=rows(batch.add_src), add_dst=rows(batch.add_dst),
            del_src=rows(batch.del_src), del_dst=rows(batch.del_dst)))

    def breaks(dep):
        monkeypatch.setattr(GraphQueryServer, "step", broken)
    return breaks


def test_a_step_that_leaves_the_graph_unchanged(monkeypatch, root):
    run = run_broken(monkeypatch, root, step_with(monkeypatch,
                                                  lambda a: a[:0]))
    refused_by(run, "wal_diff")
    refused_by(run, "topk_wrong")


def test_half_of_each_batch_left_out(monkeypatch, root):
    run = run_broken(monkeypatch, root, step_with(
        monkeypatch, lambda a: a[:a.size // 2]))
    refused_by(run, "wal_diff")
    refused_by(run, "topk_wrong")


def test_a_topk_answer_from_the_epoch_before(monkeypatch, root):
    from repro.graph import compute
    execute = SnapshotQueryEngine._execute_groups
    server = []

    def stale(self, view, queries, routed):
        got = execute(self, view, queries, routed)
        with server[0]._serve_lock:
            older = server[0]._published.get(
                Version(view.version.epoch - 1, 0).pack())
        return [tuple(np.asarray(a) for a in compute.degree_topk(
                    older, q.k, direction=q.direction))
                if isinstance(q, DegreeTopK) and older is not None else g
                for q, g in zip(queries, got)]

    def breaks(dep):
        server.append(dep.server)
        monkeypatch.setattr(SnapshotQueryEngine, "_execute_groups", stale)
    refused_by(run_broken(monkeypatch, root, breaks), "topk_wrong")


def test_ranks_shifted_past_the_tolerance(monkeypatch, root):
    pagerank = SnapshotQueryEngine.pagerank

    def shifted(self, view):
        res = pagerank(self, view)
        return dataclasses.replace(res,
                                   ranks=res.ranks * (1 + 2 * checks.RANK_TOL))

    def breaks(dep):
        monkeypatch.setattr(SnapshotQueryEngine, "pagerank", shifted)
    refused_by(run_broken(monkeypatch, root, breaks), "rank_wrong")


def test_a_pagerank_top_k_answer_one_place_down(monkeypatch, root):
    execute = SnapshotQueryEngine._execute_groups

    def shifted(self, view, queries, routed):
        got = execute(self, view, queries, routed)
        out = []
        for q, g in zip(queries, got):
            if isinstance(q, PageRankQuery) and q.top_k is not None:
                full = np.asarray(self.pagerank(view).ranks)
                ids = np.argsort(-full, kind="stable")[1:q.top_k + 1]
                g = (ids, full[ids])      # every id with its own rank
            out.append(g)
        return out

    def breaks(dep):
        monkeypatch.setattr(SnapshotQueryEngine, "_execute_groups", shifted)
    run = run_broken(monkeypatch, root, breaks)
    refused_by(run, "rank_wrong")
    assert run.checked.values["topk_wrong"] == 0


class TiedRanks:
    """A reference whose 3rd and 4th highest ranks are equal."""
    r = np.array([0.1, 0.4, 0.2, 0.3, 0.25, 0.25, 0.05])

    def ranks(self, epoch):
        return self.r

    def top_ranks(self, epoch, k):
        ids = np.argsort(-self.r, kind="stable")[:k]
        return ids, self.r[ids]


def test_a_top_k_answer_is_compared_place_by_place():
    snaps = TiedRanks()
    ids, ranks = snaps.top_ranks(0, 3)
    assert checks.rank_gap((ids, ranks), snaps, 0, 3) == 0.0
    # the 2nd to 4th: each id carries its own rank and the last equals
    # the reference's 3rd, yet the highest is missing
    down = np.argsort(-snaps.r, kind="stable")[1:4]
    assert snaps.r[down[-1]] == ranks[-1]
    assert checks.rank_gap((down, snaps.r[down]), snaps, 0, 3) == (
        pytest.approx(0.25))
    # the tie at the 3rd place may go either way
    tied = np.r_[ids[:2], 5]
    assert checks.rank_gap((tied, snaps.r[tied]), snaps, 0, 3) == 0.0
    assert checks.rank_gap((ids[:2], ranks[:2]), snaps, 0, 3) == np.inf


def test_the_stale_control_is_refused(monkeypatch, root):
    run = tiny.run_cell(monkeypatch, root, tiny.add_stream_cell(root))
    assert run.checked.correct, run.checked.values
    values = control.control_readings(run)
    assert values["topk_wrong"] > 0 and values["rank_wrong"] > 0
