"""Device time of one execution of the batched k-hop sweep program
(``_batched_khop`` in ``graph/compute.py``), from the trace."""


def read(run):
    if run.trace_summary is None:
        return None
    count, seconds = run.trace_summary.program_seconds("_batched_khop")
    return seconds / count * 1e3 if count else None
