"""The Kronecker generator and the plain reference, on the CPU."""
import numpy as np
import pytest

import chip_smoke
from bench import graphgen, reference

GRAPH = {"generator": "graph500", "scale": 11, "edge_factor": 16,
         "a": 0.57, "b": 0.19, "c": 0.19, "directed": False}


@pytest.fixture(scope="module")
def graph():
    return graphgen.generate(5, GRAPH)


def test_generator_is_deterministic_from_the_seed(graph):
    again = graphgen.generate(5, GRAPH)
    other = graphgen.generate(6, GRAPH)
    for field in ("src", "dst"):
        np.testing.assert_array_equal(getattr(graph, field),
                                      getattr(again, field))
    assert not np.array_equal(graph.src[:100], other.src[:100])


def test_seeds_beyond_32_bits_are_taken_whole():
    a = graphgen.generate(2**33 + 5, {**GRAPH, "scale": 8})
    b = graphgen.generate(5, {**GRAPH, "scale": 8})
    assert a.m and not np.array_equal(a.src, b.src)


def test_graph_is_simple_compact_and_skewed(graph):
    lo = np.minimum(graph.src, graph.dst).astype(np.int64)
    hi = np.maximum(graph.src, graph.dst).astype(np.int64)
    assert np.all(lo != hi)                             # no self-loops
    # no duplicate pair: each undirected pair is two rows, one each way
    assert np.unique(hi << 32 | lo).size == graph.m // 2
    rows = graph.dst.astype(np.int64) << 32 | graph.src
    assert np.unique(rows).size == graph.m
    deg = np.bincount(graph.src, minlength=graph.n)
    assert deg.min() >= 1 and graph.src.max() < graph.n  # compacted ids
    # a Kronecker graph's degrees are skewed: the largest is far above
    # the mean, and most vertices are far below the largest
    assert deg.max() > 20 * deg.mean()
    assert np.median(deg) < deg.mean()
    # the first half keeps each pair as drawn, the second reverses it
    half = graph.m // 2
    np.testing.assert_array_equal(graph.src[:half], graph.dst[half:])
    np.testing.assert_array_equal(graph.dst[:half], graph.src[half:])
    assert 0.4 < np.mean(graph.src[:half] < graph.dst[:half]) < 0.6
    np.testing.assert_array_equal(deg, np.bincount(graph.dst,
                                                   minlength=graph.n))


@pytest.mark.parametrize("change", [{"directed": True}, {"directed": None},
                                    {"generator": "rmat"}])
def test_a_graph_the_generator_does_not_make_is_refused(change):
    with pytest.raises(ValueError, match="undirected Graph500"):
        graphgen.generate(1, {**GRAPH, "scale": 6, **change})


def test_reference_matches_the_smoke_reference(graph):
    ours = reference.HostGraph(graph.n, graph.src, graph.dst)
    theirs = chip_smoke.HostGraph(graph.n, graph.src, graph.dst)
    rng = np.random.default_rng(0)
    for source in rng.integers(0, graph.n, 20):
        for hops in (1, 2, None):
            np.testing.assert_array_equal(ours.within_hops(source, hops),
                                          theirs.within_hops(source, hops))
    np.testing.assert_array_equal(ours.out_degree(), theirs.out_degree())


def test_traversed_edges_are_the_inner_ball_out_edges(graph):
    host = chip_smoke.HostGraph(graph.n, graph.src, graph.dst)
    ours = reference.HostGraph(graph.n, graph.src, graph.dst)
    out_deg = host.out_degree()
    for source in np.random.default_rng(1).integers(0, graph.n, 20):
        inner = np.flatnonzero(host.within_hops(source, 1))
        assert ours.traversed_edges(source, 2) == out_deg[inner].sum()
        assert ours.traversed_edges(source, 1) == out_deg[source]
