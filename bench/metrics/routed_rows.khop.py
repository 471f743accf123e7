"""Edge rows the engine's replica routing selected for the k-hop sweep
per window, before the pow2 pad (``routed_rows``)."""
from bench import counters


def read(run):
    return counters.per_window(run, counters.delta(run, "routed_rows"))
