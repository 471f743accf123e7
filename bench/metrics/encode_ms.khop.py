"""Host time per window the RPC front spent encoding and sending answers
(spans ``rpc.encode`` and ``rpc.send``)."""
from bench import counters


def read(run):
    seconds = counters.span_delta(run, "rpc.encode", "rpc.send")
    value = counters.per_window(run, seconds)
    return value * 1e3 if value is not None else None
