"""Profiler trace -> the numbers the per-layer readers need.

A traced run records the window with ``jax.profiler`` and reduces the
``.xplane.pb`` here:

* device programs: one event per executed XLA program (the device plane's
  ``XLA Modules`` line), with the benchmark span that launched it
  (``attribute``: the engine waits for each admission's and each step's
  result, so a program runs inside the span that launched it);
* busy time: the union of the device's program intervals inside the
  window (the ``XLA Ops`` line holds millions of events a window and is
  not read; inside one program the device is busy);
* idle gaps: the holes in that union, each named by the benchmark span the
  host was in at the gap's middle, and the innermost other host event
  there.

Only spans named ``bench.*`` come from the benchmark; every other host
event is JAX's or the runtime's own.

The profiler keeps a bounded number of device events, and a long window of
short programs outgrows it: the device lines then stop before the window
does.  The traced window therefore ends at the close or at the last
recorded device event, whichever comes first (a closed-loop cell keeps the
device busy to the close, so a silent tail is a cut trace, not idle time).
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
ATTRIBUTED = ("bench.admit", "bench.decode")
WINDOW = "bench.window"


@dataclass
class Event:
    name: str
    start: float  # seconds, on the trace's clock
    end: float


@dataclass
class Program(Event):
    span: str = ""


@dataclass
class Trace:
    """What one traced window holds, devices apart."""

    programs: Dict[str, List[Program]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)  # bench.* on the host
    host: List[Event] = field(default_factory=list)  # other host events
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _events(line):
    for ev in line.events:
        start = ev.start_ns * 1e-9
        yield ev.name, start, start + ev.duration_ns * 1e-9


def load(path: str, seconds: float) -> Trace:
    """Read an ``.xplane.pb``; the window is ``seconds`` long from the
    start of the host span ``bench.window``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    programs, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU:" in plane.name:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    programs[plane.name] = list(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return from_events(programs, host, seconds)


def from_events(programs: Dict[str, list], host: list,
                seconds: float) -> Trace:
    """A trace from (name, start, end) rows: device programs by device,
    and every host event (``bench.*`` spans among them)."""
    trace = Trace()
    trace.programs = {d: [Program(*row) for row in rows]
                      for d, rows in programs.items()}
    for row in host:
        (trace.spans if row[0].startswith(SPAN_PREFIX)
         else trace.host).append(Event(*row))
    windows = [s for s in trace.spans if s.name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0 = windows[0].start
    recorded = [p.end for rows in trace.programs.values() for p in rows]
    trace.window = (w0, min(w0 + seconds, max(recorded, default=w0)))
    attribute(trace)
    return trace


def attribute(trace: Trace) -> None:
    """Give each program the benchmark span that launched it.  An
    execution goes to whichever of the span it began in and the next one
    it overlaps more; then every execution of one compiled program (a
    name with its fingerprint, launched from one place in the engine)
    takes the span most of its executions went to, so that an offset
    between the device's clock and the host's cannot split it."""
    spans = sorted((s for s in trace.spans if s.name in ATTRIBUTED),
                   key=lambda s: s.start)
    starts = [s.start for s in spans]

    def overlap(i, p):
        if not 0 <= i < len(spans):
            return -1.0
        return min(p.end, spans[i].end) - max(p.start, spans[i].start)

    votes: Dict[str, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for progs in trace.programs.values():
        for p in progs:
            i = bisect.bisect_right(starts, p.start) - 1
            best = max((i, i + 1), key=lambda j: overlap(j, p))
            if overlap(best, p) > 0:
                votes[p.name][spans[best].name] += 1
    for progs in trace.programs.values():
        for p in progs:
            tally = votes.get(p.name)
            p.span = max(tally, key=tally.get) if tally else ""


def clip(events, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_intervals(trace: Trace, device: str):
    return union(clip(trace.programs[device], *trace.window))


def busy_s(trace: Trace) -> float:
    """Seconds in which a program ran, averaged over the devices."""
    devices = sorted(trace.programs)
    if not devices:
        return 0.0
    total = sum(sum(e - s for s, e in device_intervals(trace, d))
                for d in devices)
    return total / len(devices)


def idle_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest holes in the first device's busy union inside the
    window, longest first, each named by what the host was doing at its
    middle."""
    devices = sorted(trace.programs)
    if not devices:
        return []
    lo, hi = trace.window
    busy = device_intervals(trace, devices[0])
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])
    return [(host_activity(trace, (s + e) / 2), e - s) for s, e in gaps[:n]]


def host_activity(trace: Trace, t: float) -> str:
    def innermost(events):
        inside = [e for e in events if e.start <= t < e.end]
        return (min(inside, key=lambda e: e.end - e.start).name
                if inside else "")

    span = innermost([s for s in trace.spans if s.name != WINDOW])
    inner = innermost(trace.host)
    span = span or "outside bench spans"
    return f"{span} / {inner}" if inner else span


def program_seconds(trace: Trace) -> Dict[Tuple[str, str], Tuple[float, int]]:
    """(program name, launching span) -> (device seconds, executions),
    summed over the devices, for programs that began inside the window."""
    lo, hi = trace.window
    out: Dict[Tuple[str, str], List[float]] = defaultdict(lambda: [0.0, 0])
    for progs in trace.programs.values():
        for p in progs:
            if lo <= p.start < hi:
                acc = out[(p.name, p.span)]
                acc[0] += p.end - p.start
                acc[1] += 1
    return {k: (v[0], v[1]) for k, v in out.items()}


def top_programs(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    per = program_seconds(trace)
    rows = [(f"{name} @ {span or 'no span'}", secs)
            for (name, span), (secs, _) in per.items()]
    return sorted(rows, key=lambda r: -r[1])[:n]
