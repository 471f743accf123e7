"""Mean time an answered query waited in the scheduler's queue, from
submission to the drain of its window (the RPC front's batching wait
included), from the server's counters."""
from bench import counters


def read(run):
    wait = counters.delta(run, "queue_wait_s")
    served = counters.delta(run, "served")
    return wait / served * 1e3 if wait is not None and served else None
