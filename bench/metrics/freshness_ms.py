"""Median, over the epochs due in the window, of the time from an epoch's
hand-over (when the writer's schedule made it due) to the first answer
any reader received stamped at that epoch or later (host clock). An epoch
that no answer reached by the close counts at the close."""
import numpy as np


def read(run):
    due = [e for e in run.epochs if e.in_window]
    if not due:
        return None
    done = [(r.epoch, r.t_done) for r in run.answered()
            if r.epoch is not None]
    epochs = np.asarray([e for e, _ in done], np.int64)
    t_done = np.asarray([t for _, t in done], np.float64)
    waits = []
    for e in due:
        later = t_done[epochs >= e.epoch]
        first = later.min() if later.size else run.t_close
        waits.append(first - e.t_due)
    return 1e3 * float(np.median(waits))
