"""The check refuses the control and a run whose timed path is broken
underneath: answers altered where they are produced, half of each window
left out with its answers taken from the rest, and the load's log records
left unwritten. (The cell has no step in its window, and the exchange
between chips is a fault a one-chip cell cannot have.)"""
import dataclasses

import numpy as np
import pytest

from bench import control, harness
from bench.tests import tiny  # noqa: I001 (puts the program on the path)
from repro.graph import wal
from repro.graph.query import SnapshotQueryEngine


@pytest.fixture
def root(tmp_path):
    return tiny.tiny_root(tmp_path)


def broken_in_window(monkeypatch, target, name, replacement):
    """Install ``replacement`` for ``target.name`` when the window opens:
    set-up and warm-up run the program as it is."""
    measure = harness.measure

    def patched(*args, **kw):
        monkeypatch.setattr(target, name, replacement)
        return measure(*args, **kw)
    monkeypatch.setattr(harness, "measure", patched)


def test_control_is_refused(monkeypatch, root):
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    assert run.checked.correct
    values = control.control_readings(run)
    assert values["khop_wrong"] > harness.checks.LIMITS["khop_wrong"]


def altered(value):
    out = value.copy()
    out[0] = ~out[0]
    return out


def test_answers_altered_where_produced(monkeypatch, root):
    execute = SnapshotQueryEngine._execute_groups

    def wrong(self, view, queries, routed):
        return [altered(v) for v in execute(self, view, queries, routed)]
    broken_in_window(monkeypatch, SnapshotQueryEngine, "_execute_groups",
                     wrong)
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    assert not run.checked.correct
    assert run.checked.values["khop_wrong"] > 0


def test_half_of_each_window_left_out(monkeypatch, root):
    execute = SnapshotQueryEngine._execute_groups

    def half(self, view, queries, routed):
        kept = max(1, len(queries) // 2)
        got = execute(self, view, list(queries[:kept]), routed)
        return [got[i % kept] for i in range(len(queries))]
    broken_in_window(monkeypatch, SnapshotQueryEngine, "_execute_groups",
                     half)
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    assert not run.checked.correct
    assert run.checked.values["khop_wrong"] > 0


def test_load_left_out_of_the_log(monkeypatch, root):
    monkeypatch.setattr(wal.ShardWal, "append", lambda self, epoch, rows:
                        None)
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    assert not run.checked.correct
    assert run.checked.values["wal_missing"] == 1


def test_requests_left_unanswered_are_counted(monkeypatch, root):
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    run.requests[0] = dataclasses.replace(run.requests[0], ok=False,
                                          value=None)
    values, _ = harness.checks.check_answers(run.requests, run.graph)
    assert values["unanswered"] == 1


def test_a_changed_row_is_seen_in_the_log_hash(monkeypatch, root):
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    src = run.graph.src.copy()
    src[7] = (src[7] + 1) % run.graph.n
    h = harness.checks.multiset_hash
    assert h(src, run.graph.dst) != h(run.graph.src, run.graph.dst)
    order = np.random.default_rng(0).permutation(run.graph.m)
    assert h(run.graph.src[order], run.graph.dst[order]) == \
        h(run.graph.src, run.graph.dst)
