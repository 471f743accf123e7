"""Edge bytes the engine copied to the device per window
(``upload_bytes``: the routed rows, padded)."""
from bench import counters


def read(run):
    return counters.per_window(run, counters.delta(run, "upload_bytes"))
