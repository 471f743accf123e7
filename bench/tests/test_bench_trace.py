"""The trace reduction, on events recorded from a TPU v5e trace of two
2-hop query windows (``data/khop_trace.json``)."""
import json
import pathlib

import pytest

from bench import tracereduce

DATA = pathlib.Path(__file__).parent / "data" / "khop_trace.json"


@pytest.fixture(scope="module")
def events():
    raw = json.loads(DATA.read_text())
    return {"device": raw["device"], "host": [tuple(e) for e in raw["host"]]}


def test_busy_window_and_program_time(events):
    s = tracereduce.reduce(events)
    assert s.window_s == pytest.approx(16.878438206)
    # the sweep of the 16-query window is one execution of 15.02 s
    count, seconds = s.program_seconds("_batched_khop")
    assert count == 1 and seconds == pytest.approx(15.020234989)
    assert seconds < s.busy_s < s.window_s
    assert 0.10 < 1 - s.busy_s / s.window_s < 0.12


def test_breakdown_names_ops_by_program_and_gaps_by_host(events):
    s = tracereduce.reduce(events)
    ops = s.breakdown["device_ops"]
    gaps = s.breakdown["idle_gaps"]
    assert 0 < len(ops) <= tracereduce.TOP and 0 < len(gaps) <= 10
    assert ops[0][0] == "jit__batched_khop/%while"
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    idle = s.window_s - s.busy_s
    assert sum(v for _, v in gaps) == pytest.approx(idle, rel=1e-6)


def test_union_merges_and_clips():
    assert tracereduce.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == \
        [[1, 4], [5, 10]]


def test_a_trace_without_the_window_span_is_refused(events):
    host = [e for e in events["host"] if e[0] != tracereduce.WINDOW_SPAN]
    with pytest.raises(ValueError, match="bench.window"):
        tracereduce.reduce({"device": events["device"], "host": host})
