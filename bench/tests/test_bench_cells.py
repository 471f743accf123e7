"""Each cell end to end at a tiny size on the CPU (the chip gate stubbed
here), the result line, the command without a chip, and a cell added from
files alone."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny


@pytest.fixture
def root(tmp_path):
    return tiny.tiny_root(tmp_path)


@pytest.mark.parametrize("cell", [tiny.SNAPSHOT])
def test_cell_runs_correct_and_reports_its_metrics(monkeypatch, root, cell):
    run = tiny.run_cell(monkeypatch, root, cell)
    assert run.checked.correct, run.checked.values
    line = harness.result_line(run)
    assert list(line)[-1] == "checks"
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in run.cell.metrics(False)}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    for m in run.cell.metrics(True):
        # what a trace gives is not measured on the CPU
        if m["source"] != "device_trace":
            value = harness.load_metric(root, m["name"])(run)
            assert value is not None and value >= 0, m["name"]
    assert run.compiles_in_window == 0
    json.dumps(line)


def test_snapshot_cell_answers_every_client(monkeypatch, root):
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    assert {r.epoch for r in run.requests} == {0}
    assert run.checked.values["khop_wrong"] == 0
    assert run.checked.traversed     # the roofline's byte count has input


def test_snapshot_cell_logs_its_load(monkeypatch, root):
    run = tiny.run_cell(monkeypatch, root, tiny.SNAPSHOT)
    assert run.checked.values["wal_missing"] == 0
    assert run.checked.values["wal_diff"] == 0


def test_warm_up_finds_sources_by_the_shards_they_read(monkeypatch, root):
    """The shard masks the warm-up picks its sources by are those of the
    reference's expansion."""
    from bench import reference
    cell = harness.load_cell(root, tiny.SNAPSHOT)
    graph = harness.graphgen.generate(3, cell.config["graph"])
    dep = harness.Deployment(cell.config, graph, str(root / "wal"))
    try:
        mask = harness.routed_shards(dep, 2)
        host = reference.HostGraph(graph.n, graph.src, graph.dst)
        shard = dep.store.route(graph.dst)
        for v in np.random.default_rng(0).integers(0, graph.n, 25):
            inner = host.within_hops(int(v), 1)
            want = np.bitwise_or.reduce(
                np.left_shift(1, shard[inner[graph.src]]))
            assert mask[v] == want
        assert harness.routed_shards(dep, None).max() == 15
    finally:
        dep.close()


def test_rpc_front_batches_as_configured(root):
    """The RPC front waits the configuration's ``batch_wait_s`` after a
    window's first request, so a closed loop's late resends join it."""
    cell = harness.load_cell(root, tiny.SNAPSHOT)
    graph = harness.graphgen.generate(3, cell.config["graph"])
    dep = harness.Deployment(cell.config, graph, str(root / "wal"))
    try:
        assert dep.rpc.batch_wait_s == cell.config["rpc"]["batch_wait_s"]
        assert dep.rpc.batch_wait_s >= 0.1
    finally:
        dep.close()


def test_dotted_metric_names_share_a_reader(root):
    compiles = harness.load_metric(root, "compiles.snapshot")
    again = harness.load_metric(root, "compiles.another_cell")
    run = harness.Run(None, 0, 1.0, False, {}, {}, compiles_in_window=3)
    assert compiles(run) == again(run) == 3.0


def test_command_without_a_chip_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, str(tiny.REPO / "bench" / "run.py"),
         "--workload", tiny.SNAPSHOT, "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert got.returncode != 0 and got.stdout == ""
    assert "TPU" in got.stderr


def test_a_cell_is_added_from_files_alone(monkeypatch, root):
    """A new configuration, traffic mix and metric: files and entries,
    nothing else."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/g500-22-snapshot.json").read_text())
    cfg.update(name="g500-9-snapshot")
    cfg["graph"]["scale"] = 9
    (root / "bench/configs/g500-9-snapshot.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/khop1-closed4.json").write_text(json.dumps({
        "readers": [{"clients": 4,
                     "mix": [{"kind": "k_hop", "k": 1, "share": 0.5},
                             {"kind": "reachability", "max_hops": 2,
                              "share": 0.5}]}]}))
    (root / "bench/metrics/answered.khop1.py").write_text(
        "def read(run):\n    return float(len(run.answered()))\n")
    name = "g500-9-snapshot.khop1-closed4"
    spec["configs"].append({"name": "g500-9-snapshot", "source": "test",
                            "file": "bench/configs/g500-9-snapshot.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": name, "config": "g500-9-snapshot",
                              "traffic": "khop1-closed4", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append(name)
    spec["per_layer"].append({
        "name": "answered.khop1", "unit": "queries", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "query_rate", "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    run = tiny.run_cell(monkeypatch, root, name)
    assert run.checked.correct
    assert {"khop_wrong", "reach_wrong"} <= set(run.checked.values)
    assert {type(q.query).__name__ for q in run.requests} == {
        "KHop", "Reachability"}
    line = harness.result_line(run)
    assert set(line["metrics"]) == {"query_rate", "setup_s"}
    run.trace = True
    assert harness.result_line(run)["metrics"]["answered.khop1"]["value"] \
        == len(run.answered())


def test_unknown_device_kind_has_no_peaks():
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peak"):
        harness.device_peaks("TPU v9 imaginary")


def test_config_files_name_their_source_and_cuts():
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((tiny.REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"]
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert cfg["guarantees"] and "assumed" in cfg
        assert cfg["graph"]["directed"] is False
    assert pathlib.Path(tiny.REPO / "bench" / "peaks.json").exists()
