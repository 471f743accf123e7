"""Host time per window of the engine's replica routing and the pow2 pad
of the routed rows (spans ``engine.route`` and ``engine.pad``)."""
from bench import counters


def read(run):
    seconds = counters.span_delta(run, "engine.route", "engine.pad")
    value = counters.per_window(run, seconds)
    return value * 1e3 if value is not None else None
