"""Seconds the load spent applying rows on the shards (span
``store.apply``: the store's ``shard_apply_seconds``, summed over the
shards' threads, the WAL append inside it included), at the open."""
from bench import counters


def read(run):
    return counters.at_open(run, "store.apply")
