"""Seconds the load spent writing and syncing the log (spans
``wal.append`` and ``wal.fsync``, summed over the shards' threads), at
the open."""
from bench import counters


def read(run):
    return counters.at_open(run, "wal.append", "wal.fsync")
