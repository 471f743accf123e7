"""Named host spans at the layer boundaries, and the counters they feed.

Each boundary of the served read path (RPC front, scheduler, query
engine) and of the load path (store, WAL, publish) enters :class:`span`
once per call. A span is two things at once:

* a ``jax.profiler.TraceAnnotation`` of its name, with its arguments as
  metadata — recorded only while a profiler session is on, so the
  program's stages land on the device trace's clock and an idle gap on
  the chip can be read against the host stage that covers it;
* its ``perf_counter`` duration and a count, added to a :class:`Spans`
  accumulator that ``ServerStats`` exports (``span_s``, ``span_n``),
  beside the byte counters the same boundaries feed.

Spans belong in host code only: never inside a function ``jax.jit``
traces (the body would run once, at trace time), and never per row or
per hop in a loop — one per call.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import jax


class Spans:
    """Cumulative seconds and counts per span name, plus named counters
    (bytes uploaded, sent, logged). Thread-safe; ``_lock`` is a leaf
    lock: nothing else is acquired while it is held."""

    def __init__(self):
        self._lock = threading.Lock()
        self._seconds: dict[str, float] = {}
        self._counts: dict[str, int] = {}
        self._counters: dict[str, int] = {}

    def add(self, name: str, seconds: float) -> None:
        """One finished span of ``name`` that took ``seconds``."""
        with self._lock:
            self._seconds[name] = self._seconds.get(name, 0.0) + seconds
            self._counts[name] = self._counts.get(name, 0) + 1

    def count(self, counter: str, amount: int) -> None:
        """Add ``amount`` to the named counter."""
        with self._lock:
            self._counters[counter] = self._counters.get(counter, 0) + amount

    def snapshot(self) -> tuple[dict[str, float], dict[str, int],
                                dict[str, int]]:
        """``(seconds by span, count by span, counters)``, copied."""
        with self._lock:
            return (dict(self._seconds), dict(self._counts),
                    dict(self._counters))


class span:
    """Context manager for one span: the profiler annotation ``name``
    with ``args`` as its metadata (``None`` values left out), timed into
    ``totals`` on exit (``totals=None``: the annotation alone, for a
    boundary whose time another counter already keeps). :meth:`note`
    adds metadata known only once the work is done."""

    __slots__ = ("_totals", "_name", "_annotation", "_t0")

    def __init__(self, totals: Optional[Spans], name: str, **args):
        self._totals = totals
        self._name = name
        self._annotation = jax.profiler.TraceAnnotation(
            name, **{k: v for k, v in args.items() if v is not None})
        self._t0 = 0.0

    def note(self, **args) -> None:
        self._annotation.set_metadata(**args)

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        elapsed = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self._totals is not None:
            self._totals.add(self._name, elapsed)
