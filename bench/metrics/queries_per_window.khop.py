"""Queries the scheduler answered per window, from the server's counters
(delta over the measured window)."""


def read(run):
    windows = run.stats_after["windows"] - run.stats_before["windows"]
    served = run.stats_after["served"] - run.stats_before["served"]
    return served / windows if windows else None
