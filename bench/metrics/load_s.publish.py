"""Seconds the load spent publishing its snapshot (span
``serve.publish``: the stitch and the replica plan), at the open."""
from bench import counters


def read(run):
    return counters.at_open(run, "serve.publish")
