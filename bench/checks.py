"""The comparison that decides ``correct``: what the timed path produced,
against the plain reference (``reference.py``) over the loaded graph's
edge multiset, and the write-ahead log against the load.

Each compared number has its limit in ``limits.json``; a run is correct
when every number it computed is at or under its limit. The numbers:

- ``unanswered``: reads due in the window that got no answer;
- ``unsealed``: answers stamped with another version than the sealed and
  published load epoch;
- ``khop_wrong``: k-hop answers whose vertex set differs from the
  reference's;
- ``reach_wrong``: reachability answers that differ from the reference's;
- ``wal_missing``: 1 when the log, read once the store is closed, lacks
  the load epoch's commit record or an intact record of it on any shard;
- ``wal_diff``: 1 when the rows logged for the load epoch are not the
  graph's rows (by count and by a hash of the row multiset).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import struct
import zlib

import numpy as np

from bench import reference

LIMITS = json.loads(
    (pathlib.Path(__file__).parent / "limits.json").read_text())["limits"]
LOAD_EPOCH = 0


@dataclasses.dataclass
class Checked:
    values: dict                       # name -> compared number
    traversed: dict                    # k-hop source -> edges it must read

    def as_dict(self) -> dict:
        return {k: {"value": v, "limit": LIMITS[k]}
                for k, v in self.values.items()}

    @property
    def correct(self) -> bool:
        return all(v <= LIMITS[k] for k, v in self.values.items())


# --------------------------------------------------------------- the log
_HDR = struct.Struct(">IIQ")        # body length, crc32, packed version
_PACKED = struct.Struct(">Q")
K_ADD = 1                           # payload row kind (kind, src, dst, ver)


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


def check_log(graph, wal_dir: str, shards: int) -> tuple[int, int]:
    """(wal_missing, wal_diff) of the load epoch."""
    root = pathlib.Path(wal_dir)
    control = root / "control.wal"
    records = ([json.loads(body) for _, body in read_frames(control)]
               if control.exists() else [])
    committed = any(r.get("type") == "commit" and r.get("epoch") == LOAD_EPOCH
                    for r in records)
    rows = []
    for s in range(shards):
        got = [np.frombuffer(body, "<i4").reshape(-1, 4)
               for seg in sorted((root / f"shard-{s:04d}").glob("seg-*.wal"))
               for epoch, body in read_frames(seg) if epoch == LOAD_EPOCH]
        if not got:
            return 1, 1
        rows += got
    rows = np.concatenate(rows)
    adds = rows[rows[:, 0] == K_ADD]
    same = (adds.shape[0] == rows.shape[0] == graph.m
            and multiset_hash(adds[:, 1], adds[:, 2])
            == multiset_hash(graph.src, graph.dst))
    return int(not committed), int(not same)


# --------------------------------------------------------------- answers
def check_answers(requests, graph) -> tuple[dict, dict]:
    """Compare every answer with the reference on the loaded graph.
    Returns the compared numbers and, per k-hop source, the edges its
    expansion must read."""
    from repro.graph.query import query_kind
    values = {"unanswered": sum(not r.ok for r in requests)}
    ok = [r for r in requests if r.ok]
    values["unsealed"] = sum(r.epoch != LOAD_EPOCH for r in ok)
    kinds = {query_kind(r.query) for r in requests}
    unknown = kinds - {"k_hop", "reachability"}
    if unknown:
        raise ValueError(f"no reference for {sorted(unknown)}")
    host = reference.HostGraph(graph.n, graph.src, graph.dst)
    traversed = {}
    khop_wrong = reach_wrong = 0
    for r in ok:
        q = r.query
        if query_kind(q) == "reachability":
            want = bool(host.within_hops(q.src, q.max_hops)[q.dst])
            reach_wrong += bool(r.value) != want
            continue
        want = host.within_hops(q.source, q.k)
        khop_wrong += not np.array_equal(np.asarray(r.value), want)
        traversed[q.source] = host.traversed_edges(q.source, q.k)
    if "k_hop" in kinds:
        values["khop_wrong"] = khop_wrong
    if "reachability" in kinds:
        values["reach_wrong"] = reach_wrong
    return values, traversed


def check(run, wal_dir: str) -> Checked:
    """Every compared number of one run, once the system is closed."""
    values, traversed = check_answers(run.requests, run.graph)
    values["wal_missing"], values["wal_diff"] = check_log(
        run.graph, wal_dir, int(run.cell.config["store"]["shards"]))
    return Checked(values, traversed)
