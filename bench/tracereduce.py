"""From a profiler trace to the numbers the metrics read.

A traced run records the measured window with ``jax.profiler`` (host
spans of the benchmark's own: ``bench.window`` around the window,
``bench.send``/``bench.wait`` around each RPC). The reduction here reads
the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and keeps, inside the
``bench.window`` span:

- the busy time of each TPU: the union of the intervals of its ``XLA Ops``
  events (all its events where that line is missing);
- the device time of each program, by name, from the ``XLA Modules`` line;
- a breakdown: the device operations that took most time (named
  ``<program>/<op>``), and the device's
  idle gaps of 1 ms or more by what the host was doing, which is the
  shortest host event that covers the middle of the gap.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
from collections import defaultdict

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
# idle gaps shorter than this are summed under one label
SHORT_GAP_NS = 1e6
SHORT_GAP = "gaps under 1 ms"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over the chips traced
    programs: dict                      # program name -> [count, seconds]
    breakdown: dict

    def program_seconds(self, part: str) -> tuple[int, float]:
        """(executions, device seconds) of the programs whose name holds
        ``part``."""
        hits = [v for k, v in self.programs.items() if part in k]
        return (sum(c for c, _ in hits), sum(s for _, s in hits))


def extract(path: pathlib.Path) -> dict:
    """Events of an ``.xplane.pb`` as plain lists: device events by chip
    and line, and host events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    device: dict = defaultdict(lambda: defaultdict(list))
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                device[plane.name][line.name] = [
                    (e.name, e.start_ns, e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events]
    return {"device": {k: dict(v) for k, v in device.items()}, "host": host}


def union(intervals: list, lo: float, hi: float) -> list:
    """Merged intervals, clipped to [lo, hi]."""
    out: list = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(events: dict) -> Summary:
    window = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    lo, hi = window[0]
    chips = events["device"]
    if not chips:
        raise ValueError("the trace has no TPU plane")
    busy_total = 0.0
    programs: dict = defaultdict(lambda: [0, 0.0])
    ops: dict = defaultdict(float)
    gaps_by: dict = defaultdict(float)
    host = [e for e in events["host"] if e[0] != WINDOW_SPAN]
    h_name = [n for n, _, _ in host]
    h_start = np.asarray([s for _, s, _ in host], np.float64)
    h_dur = np.asarray([d for _, _, d in host], np.float64)
    for lines in chips.values():
        op_events = lines.get(OPS_LINE) or [
            e for evs in lines.values() for e in evs]
        busy = union([(s, s + d) for _, s, d in op_events], lo, hi)
        busy_total += sum(b - a for a, b in busy)
        modules = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        m_start = [s for _, s, _ in modules]
        for name, s, d in modules:
            if lo <= s < hi:
                programs[name][0] += 1
                programs[name][1] += d / 1e9
        for name, s, d in op_events:
            if lo <= s < hi:
                ops[op_label(name, s, modules, m_start)] += d / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= SHORT_GAP_NS:
                label = host_activity(h_name, h_start, h_dur, (a + b) / 2)
            elif b > a:
                label = SHORT_GAP
            else:
                continue
            gaps_by[label] += (b - a) / 1e9
    n = len(chips)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / n / 1e9,
        programs={k: list(v) for k, v in programs.items()},
        breakdown={"device_ops": [[k, v / n] for k, v in top_ops],
                   "idle_gaps": [[k, v / n] for k, v in top_gaps]})


def op_label(name: str, start: float, modules: list, m_start: list) -> str:
    """``<program>/<op>``: the op's HLO name, in the program that ran it."""
    op = name.split(" = ", 1)[0]
    i = bisect.bisect_right(m_start, start) - 1
    if i >= 0 and start < modules[i][1] + modules[i][2]:
        return f"{modules[i][0].split('(', 1)[0]}/{op}"
    return op


def host_activity(names: list, start: np.ndarray, dur: np.ndarray,
                  t: float) -> str:
    """Name of the shortest host event that covers time ``t``."""
    covers = np.flatnonzero((start <= t) & (start + dur >= t))
    if not covers.size:
        return "no host event"
    return names[covers[np.argmin(dur[covers])]]


class Tracer:
    """Profiler session around the measured window."""

    def __init__(self, directory: pathlib.Path):
        self.dir = directory

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans and runtime only
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self) -> Summary:
        import jax
        jax.profiler.stop_trace()
        files = sorted(self.dir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return reduce(extract(files[-1]))
