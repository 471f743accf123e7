"""A cell with a writer: the tiny stream cell added from files alone runs
end to end through ``harness.main``; a mix without a writer keeps the
reader path; the update stream and the references at each epoch."""
import dataclasses
import json

import numpy as np
import pytest

from bench import graphgen, harness, reference, streamgen
from bench.tests import tiny

GRAPH = {"generator": "graph500", "scale": 8, "edge_factor": 16,
         "a": 0.57, "b": 0.19, "c": 0.19, "directed": False}
STREAM = {"generator": "rmat", "a": 0.5, "b": 0.1, "c": 0.1,
          "pairs_per_epoch": 128, "epochs": 30}


@pytest.fixture
def root(tmp_path):
    return tiny.tiny_root(tmp_path)


def test_stream_cell_from_data_files_runs_correct(monkeypatch, root,
                                                  capsys):
    name = tiny.add_stream_cell(root)
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    assert harness.main(["--workload", name, "--seed", str(2**40 + 7),
                         "--seconds", "1.5", "--trace", "0"], root=root,
                        gate=lambda chips: tiny.DEVICE) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] > 0 and not line["failed"]
    assert set(line["metrics"]) == {"query_rate", "setup_s", "freshness_ms"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert {"topk_wrong", "rank_wrong", "seal_failed", "unsynced",
            "wal_missing", "wal_diff"} <= set(line["checks"])
    assert list(line)[-1] == "checks"


def test_a_mix_without_a_writer_keeps_the_reader_path(monkeypatch, root):
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    assert [(e.epoch, e.published) for e in run.epochs] == [(0, True)]
    assert run.stream is None
    assert set(run.checked.values) == {"unanswered", "unsealed",
                                       "khop_wrong", "wal_missing",
                                       "wal_diff"}
    assert "freshness_ms" not in harness.result_line(run)["metrics"]
    assert harness.load_metric(root, "freshness_ms")(run) is None


def test_a_configuration_sets_the_query_servers_own_settings(root, tmp_path):
    cell = harness.load_cell(root, tiny.add_stream_cell(root))
    graph = graphgen.generate(3, cell.config["graph"])
    for config, keep in ((cell.config, 2),
                         ({k: v for k, v in cell.config.items()
                           if k != "server"}, 8)):
        dep = harness.Deployment(config, graph, str(tmp_path / f"w{keep}"))
        try:
            assert dep.server.view_keep == keep
        finally:
            dep.close()
    # a setting the harness does not take by name is refused, not ignored
    with pytest.raises(ValueError, match="rank_keep"):
        harness.Deployment(dict(cell.config, server={"rank_keep": 1}),
                           graph, str(tmp_path / "w"))


@pytest.fixture(scope="module")
def stream():
    graph = graphgen.generate(11, GRAPH)
    return graph, streamgen.generate(11, GRAPH, STREAM, graph)


def keys(pairs):
    return set(streamgen.pair_keys(*pairs).tolist())


def test_stream_keeps_the_live_count_and_deletes_the_oldest(stream):
    graph, st = stream
    p = st.pairs_per_epoch
    # the run reaches past the base, so the stream's own pairs are deleted
    assert st.epochs * p > st.base_pairs
    assert keys(st.live_pairs(0)) == keys((graph.src[:graph.m // 2],
                                           graph.dst[:graph.m // 2]))
    base_deg = np.bincount(graph.src, minlength=graph.n)
    born = {k: 0 for k in keys(st.live_pairs(0))}
    for e in range(1, st.epochs + 1):
        before = keys(st.live_pairs(e - 1))
        add_rows, del_rows = st.adds(e), st.deletes(e)
        added, deleted = keys(add_rows), keys(del_rows)
        live = st.live_pairs(e)
        assert live[0].size == st.base_pairs == len(keys(live))
        assert len(added) == len(deleted) == p
        assert not added & before                  # new pairs only
        assert deleted <= before
        assert keys(live) == (before - deleted) | added
        # first in, first out: nothing deleted is younger than what stays
        assert max(born[k] for k in deleted) <= min(
            born[k] for k in before - deleted)
        born.update({k: e for k in added})
        for rows in (add_rows, del_rows):          # both orientations
            np.testing.assert_array_equal(rows[0][:p], rows[1][p:])
            assert np.all(rows[0] != rows[1])
        assert np.all(base_deg[add_rows[0]] > 0)   # vertices of the base
    assert 0 < st.redrawn_share < 1


def test_stream_is_deterministic_from_the_seed(stream):
    graph, st = stream
    again = streamgen.generate(11, GRAPH, STREAM, graph)
    np.testing.assert_array_equal(st.src, again.src)
    other_graph = graphgen.generate(12, GRAPH)
    other = streamgen.generate(12, GRAPH, STREAM, other_graph)
    assert not np.array_equal(st.src[st.base_pairs:st.base_pairs + 50],
                              other.src[other.base_pairs:
                                        other.base_pairs + 50])


@pytest.mark.parametrize("case", ["two writers", "no stream",
                                  "too few epochs", "past the epoch limit"])
def test_a_writer_its_stream_cannot_feed_is_refused(root, case):
    cell = harness.load_cell(root, tiny.add_stream_cell(root))
    harness.check_writers(cell, 1.5)
    config, traffic = json.loads(json.dumps([cell.config, cell.traffic]))
    if case == "two writers":
        traffic["writers"] *= 2
    elif case == "no stream":
        del config["stream"]
    elif case == "too few epochs":
        config["stream"]["epochs"] = 10       # a 1.5 s run steps 16
    else:
        config["stream"]["epochs"] = 2048     # the store stamps 2047
    with pytest.raises(ValueError):
        harness.check_writers(
            dataclasses.replace(cell, config=config, traffic=traffic), 1.5)


def test_degree_topk_reference_is_the_brute_force_order():
    rng = np.random.default_rng(4)
    n = 40
    src, dst = rng.integers(0, n, 300), rng.integers(0, n, 300)
    for direction, ends in (("in", dst), ("out", src)):
        deg = np.bincount(ends, minlength=n)
        want = sorted(range(n), key=lambda v: (-deg[v], v))[:12]
        ids, got = reference.degree_topk(n, src, dst, 12, direction)
        assert ids.tolist() == want and got.tolist() == deg[want].tolist()
        assert len(set(deg[want])) < len(want)       # ties to resolve


def dense_pagerank(n, src, dst):
    """The server's iteration, dense, over both orientations of each
    pair, run far past convergence."""
    a = np.zeros((n, n))
    np.add.at(a, (dst, src), 1.0)
    np.add.at(a, (src, dst), 1.0)
    out = a.sum(axis=0)
    pr = np.full(n, 1.0 / n)
    for _ in range(500):
        lost = pr[out == 0].sum()
        pr = (1 - reference.DAMPING) / n + reference.DAMPING * (
            a @ np.where(out > 0, pr / np.maximum(out, 1), 0.0) + lost / n)
    return pr


def test_pagerank_reference_matches_a_dense_power_iteration():
    rng = np.random.default_rng(5)
    n = 30
    # pairs among the first 25 vertices: the last five are isolated, so
    # their rank is spread evenly; one pair is repeated (two rows each way)
    src, dst = rng.integers(0, n - 5, 200), rng.integers(0, n - 5, 200)
    keep = src != dst
    src, dst = np.r_[src[keep], 3], np.r_[dst[keep], 4]
    src, dst = np.r_[src, 3], np.r_[dst, 4]
    got = reference.pagerank(n, src, dst)
    assert got.dtype == np.float64 and got.shape == (n,)
    np.testing.assert_allclose(got, dense_pagerank(n, src, dst), rtol=1e-9)
    assert abs(got.sum() - 1.0) < 1e-12


def test_ranks_of_a_batch_of_epochs_are_each_epochs_own(stream,
                                                        monkeypatch):
    graph, st = stream
    snaps = reference.Snapshots(graph, st)
    solved = []
    pagerank = reference.pagerank

    def counted(n, src, dst):
        solved.append(src.size)
        return pagerank(n, src, dst)
    monkeypatch.setattr(reference, "pagerank", counted)
    for _ in range(2):
        for e in (3, 5, 9):
            np.testing.assert_allclose(
                snaps.ranks(e), dense_pagerank(graph.n, *st.live_pairs(e)),
                rtol=1e-9)
    assert len(solved) == 3          # each epoch solved once
    at5 = snaps.ranks(5)
    ids, ranks = snaps.top_ranks(5, 10)
    want = sorted(range(graph.n), key=lambda v: (-at5[v], v))[:10]
    assert ids.tolist() == want and ranks.tolist() == at5[want].tolist()


def test_freshness_is_the_median_wait_for_a_stamped_answer(root):
    read = harness.load_metric(root, "freshness_ms")
    run = harness.Run(None, 0, 1.0, False, {}, {}, t_open=10.0, t_close=20.0)
    run.epochs = [harness.Epoch(0, 0.0, published=True),
                  harness.Epoch(1, 10.0, in_window=True),
                  harness.Epoch(2, 13.0, in_window=True),
                  harness.Epoch(3, 16.0, in_window=True)]

    def answer(epoch, t_done):
        return harness.Request(None, t_sent=t_done - 0.1, t_done=t_done,
                               ok=True, epoch=epoch)
    # epoch 1 first read at 11.5; epoch 2 only through an answer at epoch
    # 3 (16.5); epoch 3 at 16.5 too
    run.requests = [answer(0, 10.5), answer(1, 11.5), answer(1, 14.0),
                    answer(3, 16.5)]
    assert read(run) == pytest.approx(1e3 * np.median([1.5, 3.5, 0.5]))
    # an epoch that no answer reaches counts at the close
    run.requests = run.requests[:3]
    assert read(run) == pytest.approx(1e3 * np.median([1.5, 7.0, 4.0]))
