"""The ``routed_rows.khop`` reader: the routed rows per window of the tiny
cell, and nothing, without raising, from a program that lacks the
counter."""
import json

from bench import harness
from bench.tests import tiny

METRIC = "routed_rows.khop"


def test_routed_rows_reads_the_tiny_cell(monkeypatch, tmp_path):
    root = tiny.tiny_root(tmp_path)
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    assert run.checked.correct
    rows = harness.load_metric(root, METRIC)(run)
    upload = harness.load_metric(root, "upload_bytes.khop")(run)
    # two int32 arrays of the rows, padded to a power of two
    assert 0 < 8 * rows <= upload
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    entry = {m["name"]: m for m in spec["per_layer"]}[METRIC]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "query engine"
    assert entry["workloads"] == [tiny.SNAPSHOT]


def test_routed_rows_reads_nothing_without_the_counter(tmp_path):
    root = tiny.tiny_root(tmp_path)
    read = harness.load_metric(root, METRIC)
    before = {"windows": 4, "served": 64, "upload_bytes": 10}
    after = {"windows": 9, "served": 144, "upload_bytes": 50}
    assert read(harness.Run(None, 0, 1.0, True, {}, {},
                            stats_before=before,
                            stats_after=after)) is None
    before["routed_rows"], after["routed_rows"] = 100, 600
    assert read(harness.Run(None, 0, 1.0, True, {}, {},
                            stats_before=before,
                            stats_after=after)) == 100.0
