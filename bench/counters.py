"""The server's stage counters (``ServerStats``: ``span_s``, ``span_n``,
``queue_wait_s`` and the byte counters) as the metric readers take them.

A program without these counters (a commit from before they existed)
reads as ``None`` here, never as an error, so its result line leaves the
metric out.
"""
from __future__ import annotations

from typing import Optional


def delta(run, field: str) -> Optional[float]:
    """Growth of a ``ServerStats`` field over the measured window."""
    if field not in run.stats_before:
        return None
    return run.stats_after[field] - run.stats_before[field]


def span_delta(run, *names: str) -> Optional[float]:
    """Seconds the named spans grew by over the measured window."""
    if "span_s" not in run.stats_before:
        return None
    before, after = run.stats_before["span_s"], run.stats_after["span_s"]
    return sum(after.get(n, 0.0) - before.get(n, 0.0) for n in names)


def at_open(run, *names: str) -> Optional[float]:
    """Seconds the named spans held when the window opened: the load's
    (the warm-up writes no span these name)."""
    if "span_s" not in run.stats_before:
        return None
    return sum(run.stats_before["span_s"].get(n, 0.0) for n in names)


def per_window(run, value: Optional[float]) -> Optional[float]:
    """``value`` over the windows the scheduler answered in the window."""
    windows = delta(run, "windows")
    return value / windows if value is not None and windows else None
