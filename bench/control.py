"""The control of the correctness check: the plain reference put in the
program's place with one stated guarantee broken. The check has to refuse
it, and its readings set the upper end of each limit (PERF.md).

    python3 bench/control.py --workload <name> --seed <n> --seconds <s> \
        [--trace 1]

Runs the cell as ``bench/run.py`` does (set-up, a window, the check of the
program's answers), then answers the same requests with the control and
checks those too. Prints one JSON line: each compared number as the
program read it and as the control read it, and the run's own result
line (with ``--trace 1``, its per-layer metrics). Benchmark runs never
run it.

The control answers every request from a partly sealed snapshot: the load
epoch applied to three quarters of the rows only (those whose destination
is not 0 mod 4), which breaks "answers at sealed snapshots only". The
configuration states no precision, and its answers are exact sets, so
there is no lower precision to compute them in. In a run with an update
stream the control is stale instead: each answer stamped at an epoch
``e >= 1`` is the reference's answer at ``e - 1`` (answers stamped at the
load have no older snapshot and are left out), which breaks "answers at
the stamped snapshot".
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import checks, graphgen, harness, reference  # noqa: E402


def partial_graph(graph: graphgen.Graph) -> graphgen.Graph:
    """The load epoch with its rows whose destination is 0 mod 4 left
    unapplied."""
    keep = graph.dst % 4 != 0
    return dataclasses.replace(graph, src=graph.src[keep],
                               dst=graph.dst[keep])


def answer(snaps: reference.Snapshots, q, epoch: int):
    """The reference's answer to ``q`` at ``epoch``, as the server shapes
    it."""
    from repro.graph.query import query_kind
    kind = query_kind(q)
    if kind == "degree_topk":
        return snaps.degree_topk(epoch, q.k, q.direction)
    if kind == "pagerank":
        if q.top_k is None:
            return snaps.ranks(epoch)
        return snaps.top_ranks(epoch, q.top_k)
    host = snaps.host(epoch)
    if kind == "k_hop":
        return host.within_hops(q.source, q.k)
    return bool(host.within_hops(q.src, q.max_hops)[q.dst])


def compared(run) -> list:
    """The answers the check compared, oldest epoch first: PageRank
    answers only at its sampled epochs (``run.checked.rank_at``)."""
    from repro.graph.query import query_kind
    rank_at = run.checked.rank_at
    return sorted((r for r in run.answered() if r.epoch is not None and (
        query_kind(r.query) != "pagerank" or rank_at is None
        or r.epoch in rank_at)), key=lambda r: r.epoch)


def stale_readings(run, snaps: reference.Snapshots) -> dict:
    """Each compared number of the stale control's answers."""
    published = [e.epoch for e in run.epochs if e.published]
    stale = [dataclasses.replace(r, value=answer(snaps, r.query, r.epoch - 1))
             for r in compared(run) if r.epoch >= 1]
    values, _ = checks.check_answers(stale, run.graph, published, snaps)
    return values


def control_readings(run, snaps=None) -> dict:
    """Each compared number of the answers as the control reads it
    (``snaps``: the reference's answers to reuse, such as the check's)."""
    from repro.graph.query import query_kind
    if run.stream is not None:
        return stale_readings(run, snaps or reference.Snapshots(
            run.graph, run.stream))
    part = partial_graph(run.graph)
    host = reference.HostGraph(part.n, part.src, part.dst)
    answered = []
    for r in run.requests:
        if not r.ok:
            continue
        q = r.query
        if query_kind(q) == "k_hop":
            value = host.within_hops(q.source, q.k)
        else:
            value = bool(host.within_hops(q.src, q.max_hops)[q.dst])
        answered.append(dataclasses.replace(r, value=value))
    values, _ = checks.check_answers(answered, run.graph)
    return values


def main(argv=None, *, root: pathlib.Path = ROOT,
         gate=harness.require_chips) -> int:
    ap = argparse.ArgumentParser(description="Read the control's numbers.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    started = harness.process_start_time()
    cell = harness.load_cell(root, args.workload)
    device = gate(cell.chips)
    run = harness.execute(cell, args.seed, args.seconds, bool(args.trace),
                          device, started)
    t0 = time.time()
    control = control_readings(run)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "program": run.checked.values, "control": control,
                      "control_s": time.time() - t0,
                      "answered": len(run.answered()),
                      "result": harness.result_line(run)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
