"""The program's own spans on the device trace's clock.

``deeplearning4j_tpu.monitor.trace`` mirrors every span it opens into the
profiler as a ``jax.profiler.TraceAnnotation`` named ``dl4j.<span>`` whose
scalar attrs come back as event stats. ``lib/xplane.load_xplane`` keeps only
the harness's own ``bench.*`` host events, so ``load`` opens the xplane file
again: the one under ``.bench_out/trace-*/`` whose ``bench.trace_window`` event
is the loaded trace's window to the nanosecond. A ``Trace`` whose ``host``
list already holds ``dl4j.*`` events (a made-up trace in a test; a loader that
keeps them) is read as it is. A program that opens no such span, as the one
before PR 23, gives an empty list, and every reader built on it nothing.

Beside ``load``: the two joins the span metrics share -- device-idle time
inside a set of host spans (one device, the lowest-numbered, inside the traced
window), and the program launches inside one. A launch is the runtime's own
host event round each call of ``PJRT_LoadedExecutable_Execute``, one per
execution of a program on the device, and it lies on the HOST's clock like
the spans. The device's timeline does not: in one of two traces of one cell it
lay 0.9 ms before the host's (a program seemed to start that long before its
launch; PERF.md section 6, PR 23), which moves a program that starts near a
span's edge into the neighbouring span. So programs are counted where they
were launched, and ``device_idle`` first moves the device's intervals by
``device_lead_ns``, the lead that causality shows.
"""

from __future__ import annotations

import functools
import glob
import os
import statistics
import sys
from typing import Iterable, List, Optional

from . import xplane
from .xplane import Event, Interval, Trace

PREFIX = "dl4j."
LAUNCH = "PJRT_LoadedExecutable_Execute"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WINDOW = xplane.HOST_PREFIX + "trace_window"


def load(trace: Trace, out_dir: Optional[str] = None) -> List[Event]:
    """The ``dl4j.*`` host events of the run ``trace`` came from, sorted by
    start (longer first on ties), on ``trace``'s clock."""
    return [e for e in _host_events(trace, out_dir)
            if e.name.startswith(PREFIX)]


def launches(trace: Trace, out_dir: Optional[str] = None) -> List[Event]:
    """The runtime's program-launch events of that run, sorted by start."""
    return [e for e in _host_events(trace, out_dir) if e.name == LAUNCH]


def _host_events(trace: Trace, out_dir: Optional[str]) -> Iterable[Event]:
    if any(e.name.startswith(PREFIX) for e in trace.host):
        return trace.host
    lo, hi = trace.window()
    if hi <= lo:
        return ()
    return _reopen(out_dir or os.path.join(ROOT, ".bench_out"), lo, hi)


def read_host_events(path: str) -> Iterable[Event]:
    """Of one xplane file's host planes: the harness's ``bench.*`` events,
    the program's spans and the runtime's launches."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in xplane._events(line, None):
                    if e.name.startswith((PREFIX, xplane.HOST_PREFIX)) \
                            or e.name == LAUNCH:
                        yield e


@functools.lru_cache(maxsize=2)     # several readers, one file
def _reopen(out_dir: str, lo: float, hi: float) -> tuple:
    for log_dir in sorted(glob.glob(os.path.join(out_dir, "trace-*"))):
        path = xplane.find_xplane(log_dir)
        if path is None:
            continue
        events = sorted(read_host_events(path),
                        key=lambda e: (e.start, -e.dur))
        if any(e.name == WINDOW and (e.start, e.end) == (lo, hi)
               for e in events):
            return tuple(e for e in events
                         if not e.name.startswith(xplane.HOST_PREFIX))
    print(f"program_spans: no xplane under {out_dir}/trace-* has the "
          f"window {lo:.0f}..{hi:.0f}; the program's spans are not read",
          file=sys.stderr)
    return ()


def named(events: Iterable[Event], name: str) -> List[Event]:
    """The events of the span ``name`` (``serve.admit``)."""
    return [e for e in events if e.name == PREFIX + name]


def intervals(events: Iterable[Event]) -> List[Interval]:
    """Disjoint and sorted, as ``xplane.subtract`` wants them."""
    return xplane.union((e.start, e.end) for e in events)


def device_lead_ns(trace: Trace, device: Optional[int] = None) -> float:
    """By how much the device's timeline lies BEFORE the host's in this
    trace, as causality shows it: the median, over the device's program
    executions paired in order with their launches, of launch start less
    device start. 0 where that is not positive (the clocks agree; programs
    queue behind one another), and where launches and executions differ in
    number, so that they cannot be paired."""
    if not trace.devices:
        return 0.0
    d = trace.devices[min(trace.devices) if device is None else device]
    starts = sorted(e.start for e in d.modules)
    launched = launches(trace)
    if not starts or len(starts) != len(launched):
        return 0.0
    return max(0.0, statistics.median(
        e.start - s for e, s in zip(launched, starts)))


def device_idle(trace: Trace, device: Optional[int] = None) -> List[Interval]:
    """The intervals of the window in which no op ran on the device, on the
    host's clock (``device_lead_ns``)."""
    if not trace.devices:
        return []
    d = trace.devices[min(trace.devices) if device is None else device]
    lo, hi = trace.window()
    lead = device_lead_ns(trace, device)
    busy = xplane.clip(xplane.union((e.start + lead, e.end + lead)
                                    for e in d.ops or d.modules), lo, hi)
    return xplane.subtract([(lo, hi)], busy)


def overlap_ns(a: List[Interval], b: List[Interval]) -> float:
    """Nanoseconds in both of two disjoint, sorted interval lists."""
    return xplane.total(a) - xplane.total(xplane.subtract(a, b))


def starts_inside(events: Iterable[Event], spans: List[Interval]) -> int:
    """How many of ``events`` start inside one of the disjoint, sorted
    ``spans``."""
    starts = sorted(e.start for e in events)
    n = i = 0
    for a, b in spans:
        while i < len(starts) and starts[i] < a:
            i += 1
        while i < len(starts) and starts[i] < b:
            n += 1
            i += 1
    return n
