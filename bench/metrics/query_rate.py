"""Answered queries per second over the whole window, from the first send
to the last answer (host clock)."""


def read(run):
    done = run.answered()
    if not done:
        return None
    first = min(r.t_sent for r in run.requests)
    return len(done) / (max(r.t_done for r in done) - first)
