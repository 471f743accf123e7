"""The comparison that decides ``correct``: what the timed path produced,
against the plain reference (``reference.py``) over the live edge
multiset of each stamped epoch, and the write-ahead log against every
acknowledged epoch.

Each compared number has its limit in ``limits.json``; a run is correct
when every number it computed is at or under its limit. The numbers:

- ``unanswered``: reads due in the window that got no answer;
- ``unsealed``: answers stamped with another version than an acknowledged
  epoch's: the load, or an epoch whose step returned with it published;
- ``khop_wrong``: k-hop answers whose vertex set differs from the
  reference's;
- ``reach_wrong``: reachability answers that differ from the reference's;
- ``topk_wrong``: degree top-k answers whose ids or degrees differ from
  the reference's (ties go to the lower id);
- ``rank_wrong``: PageRank answers with a rank off the reference's by
  more than ``RANK_TOL`` of it, at up to ``RANK_EPOCHS`` stamped epochs
  drawn from the seed: each returned id's rank against the reference's
  rank of that id, and a top-k answer's i-th rank against the
  reference's i-th;
- ``seal_failed``: epochs the writer handed over whose step returned with
  the epoch unpublished;
- ``unsynced``: under fsync ``always``, epochs whose step returned before
  each shard's log and the control log were fsynced;
- ``wal_missing``: acknowledged epochs whose commit record, or whose
  intact record on any shard, the log lacks, read once the store is
  closed;
- ``wal_diff``: acknowledged epochs whose logged rows are not the epoch's
  rows: its adds and its deletes, each by count and by a hash of the row
  multiset.

The answers' numbers are computed for the kinds a run sent, and the
writer's for a run with a writer; a run without one logs the load alone.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import struct
import zlib
from typing import Optional

import numpy as np

from bench import reference

LIMITS = json.loads(
    (pathlib.Path(__file__).parent / "limits.json").read_text())["limits"]
LOAD_EPOCH = 0
# how far a served rank may lie from the reference's, as a share of the
# reference's rank. The server iterates in float32 from an older
# version's ranks until an L1 residual of 1e-6; the reference's own error
# is far below that (reference.CG_TOL). The tolerance sits between the
# sound runs' largest gap and the stale control's smallest; the readings
# are in PERF.md.
RANK_TOL = 1e-3
# PageRank answers are compared at no more than this many of the epochs
# they are stamped with, drawn from the seed (every answer at those
# epochs): each epoch costs the reference a solve over every live row
RANK_EPOCHS = 2


@dataclasses.dataclass
class Checked:
    values: dict                       # name -> compared number
    traversed: dict                    # k-hop source -> edges it must read
    reference: Optional[reference.Snapshots] = None   # what it compared to
    rank_at: Optional[set] = None      # epochs of the PageRank compared

    def as_dict(self) -> dict:
        return {k: {"value": v, "limit": LIMITS[k]}
                for k, v in self.values.items()}

    @property
    def correct(self) -> bool:
        return all(v <= LIMITS[k] for k, v in self.values.items())


# --------------------------------------------------------------- the log
_HDR = struct.Struct(">IIQ")        # body length, crc32, packed version
_PACKED = struct.Struct(">Q")
K_ADD, K_DEL = 1, 2                 # payload row kinds (kind, src, dst, ver)


def read_frames(path: pathlib.Path) -> list[tuple[int, bytes]]:
    """The intact records of one log file, as (epoch, body); reading stops
    at the first torn or corrupt record."""
    data = path.read_bytes()
    out = []
    off = 0
    while off + _HDR.size <= len(data):
        length, crc, packed = _HDR.unpack_from(data, off)
        end = off + _HDR.size + length
        if end > len(data):
            break
        body = data[off + _HDR.size:end]
        if zlib.crc32(body, zlib.crc32(_PACKED.pack(packed))) != crc:
            break
        out.append((packed >> 32, body))
        off = end
    return out


def multiset_hash(src: np.ndarray, dst: np.ndarray) -> int:
    """An order-free hash of a row multiset: the sum, modulo 2**64, of a
    SplitMix64 finaliser of each (src, dst) key."""
    z = (np.asarray(dst, np.uint64) << np.uint64(32)) | np.asarray(
        src, np.uint32).astype(np.uint64)
    with np.errstate(over="ignore"):
        z = z + np.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
        return int(z.sum(dtype=np.uint64))


def same_rows(rows: np.ndarray, adds: tuple, deletes: tuple) -> bool:
    """Whether logged payload rows are exactly ``adds`` and ``deletes``
    (each a ``(src, dst)`` pair of arrays), by count and row multiset."""
    got_add = rows[rows[:, 0] == K_ADD]
    got_del = rows[rows[:, 0] == K_DEL]
    return (got_add.shape[0] + got_del.shape[0] == rows.shape[0]
            and got_add.shape[0] == adds[0].size
            and got_del.shape[0] == deletes[0].size
            and multiset_hash(got_add[:, 1], got_add[:, 2])
            == multiset_hash(*adds)
            and multiset_hash(got_del[:, 1], got_del[:, 2])
            == multiset_hash(*deletes))


def epoch_rows(graph, stream, epoch: int) -> tuple[tuple, tuple]:
    """(adds, deletes) of an epoch, from the generated graph and stream."""
    if epoch == LOAD_EPOCH:
        none = np.zeros(0, np.int32)
        return (graph.src, graph.dst), (none, none)
    return stream.adds(epoch), stream.deletes(epoch)


def check_log(graph, wal_dir: str, shards: int, stream=None,
              epochs=(LOAD_EPOCH,)) -> tuple[int, int]:
    """(wal_missing, wal_diff): the acknowledged ``epochs`` the log lacks,
    and those it holds with other rows than the epoch's."""
    root = pathlib.Path(wal_dir)
    control = root / "control.wal"
    records = ([json.loads(body) for _, body in read_frames(control)]
               if control.exists() else [])
    committed = {r.get("epoch") for r in records if r.get("type") == "commit"}
    wanted = set(epochs)
    logged = [{} for _ in range(shards)]
    for s, per in enumerate(logged):
        for seg in sorted((root / f"shard-{s:04d}").glob("seg-*.wal")):
            for epoch, body in read_frames(seg):
                if epoch in wanted:
                    per.setdefault(epoch, []).append(
                        np.frombuffer(body, "<i4").reshape(-1, 4))
    missing = diff = 0
    for e in sorted(wanted):
        if any(e not in per for per in logged):
            missing += 1
            diff += 1
            continue
        missing += e not in committed
        rows = np.concatenate([r for per in logged for r in per[e]])
        diff += not same_rows(rows, *epoch_rows(graph, stream, e))
    return missing, diff


# --------------------------------------------------------------- answers
# the number each query kind's wrong answers are counted under
WRONG = {"k_hop": "khop_wrong", "reachability": "reach_wrong",
         "degree_topk": "topk_wrong", "pagerank": "rank_wrong"}


def rank_gap(value, snaps: reference.Snapshots, epoch: int,
             top_k: Optional[int] = None) -> float:
    """Largest gap of a PageRank answer from the reference's ranks at
    ``epoch``, as a share of the reference's: over each rank returned
    against the reference's rank of its id and, for a ``top_k`` answer,
    place by place against the reference's ``top_k`` highest, so that an
    answer that leaves out or misplaces one of them is off however right
    each of its own ranks is (infinite where it has another length)."""
    want = snaps.ranks(epoch)
    if top_k is None:
        got = np.asarray(value, np.float64)
        return float(np.max(np.abs(got - want) / want))
    ids, ranks = (np.asarray(v) for v in value)
    ranks = ranks.astype(np.float64)
    top = snaps.top_ranks(epoch, top_k)[1]
    if ranks.shape != top.shape or ids.shape != top.shape:
        return float("inf")
    return float(max(np.max(np.abs(ranks - want[ids]) / want[ids]),
                     np.max(np.abs(ranks - top) / top)))


def rank_epochs(requests, published, seed: int) -> set:
    """The epochs at which PageRank answers are compared: every published
    epoch one is stamped with, or ``RANK_EPOCHS`` of them drawn from
    ``seed``."""
    from repro.graph.query import query_kind
    stamped = sorted({r.epoch for r in requests if r.ok
                      and query_kind(r.query) == "pagerank"
                      and r.epoch in set(published)})
    if len(stamped) <= RANK_EPOCHS:
        return set(stamped)
    rng = np.random.default_rng([seed, 2])
    return set(rng.choice(stamped, RANK_EPOCHS, replace=False).tolist())


def check_answers(requests, graph, published=(LOAD_EPOCH,),
                  snapshots=None, rank_at=None) -> tuple[dict, dict]:
    """Compare every answer with the reference at its stamped epoch
    (``snapshots``: a ``reference.Snapshots``; by default the loaded graph
    alone), PageRank answers only at the epochs of ``rank_at`` (``None``:
    at all). Returns the compared numbers and, per k-hop source, the
    edges its expansion must read."""
    from repro.graph.query import query_kind
    snaps = snapshots or reference.Snapshots(graph)
    values = {"unanswered": sum(not r.ok for r in requests)}
    ok = [r for r in requests if r.ok]
    published = set(published)
    values["unsealed"] = sum(r.epoch not in published for r in ok)
    kinds = {query_kind(r.query) for r in requests}
    unknown = kinds - set(WRONG)
    if unknown:
        raise ValueError(f"no reference for {sorted(unknown)}")
    wrong = {WRONG[k]: 0 for k in kinds}
    traversed = {}
    # epoch by epoch, so that one epoch's adjacency is built once
    for r in sorted(ok, key=lambda r: -1 if r.epoch is None else r.epoch):
        if r.epoch not in published or not snaps.has(r.epoch):
            continue
        q, kind = r.query, query_kind(r.query)
        if kind == "pagerank" and rank_at is not None \
                and r.epoch not in rank_at:
            continue
        if kind == "k_hop":
            host = snaps.host(r.epoch)
            bad = not np.array_equal(np.asarray(r.value),
                                     host.within_hops(q.source, q.k))
            traversed[q.source] = host.traversed_edges(q.source, q.k)
        elif kind == "reachability":
            want = snaps.host(r.epoch).within_hops(q.src, q.max_hops)[q.dst]
            bad = bool(r.value) != bool(want)
        elif kind == "degree_topk":
            ids, degrees = snaps.degree_topk(r.epoch, q.k, q.direction)
            got_ids, got_degrees = (np.asarray(v) for v in r.value)
            bad = not (np.array_equal(got_ids, ids)
                       and np.array_equal(got_degrees, degrees))
        else:
            bad = not rank_gap(r.value, snaps, r.epoch, q.top_k) <= RANK_TOL
        wrong[WRONG[kind]] += bad
    values.update(wrong)
    return values, traversed


def check(run, wal_dir: str) -> Checked:
    """Every compared number of one run, once the system is closed."""
    published = [e.epoch for e in run.epochs if e.published]
    snaps = reference.Snapshots(run.graph, run.stream)
    rank_at = rank_epochs(run.requests, published, run.seed)
    values, traversed = check_answers(run.requests, run.graph, published,
                                      snaps, rank_at)
    store = run.cell.config["store"]
    shards = int(store["shards"])
    handed = [e for e in run.epochs
              if e.epoch != LOAD_EPOCH and e.t_call is not None]
    if run.cell.traffic.get("writers"):
        values["seal_failed"] = sum(not e.published for e in handed)
        if store["wal_fsync"] == "always" and all(
                e.span_n is not None for e in handed):
            values["unsynced"] = sum(
                e.span_n.get("wal.fsync", 0) < shards + 1 for e in handed)
    values["wal_missing"], values["wal_diff"] = check_log(
        run.graph, wal_dir, shards, run.stream, published)
    return Checked(values, traversed, snaps, rank_at)
