"""Shared by the span metrics: the program's ``dl4j.*`` spans joined to the
device trace (``lib/program_spans.py``). Every function returns ``None`` where
the trace holds no such span (a program from before PR 23; an untraced run).
"""

import statistics
import sys

from benchmarks.lib import program_spans as ps
from benchmarks.lib import xplane


def spans(trace, name):
    return ps.named(ps.load(trace), name)


def idle_pct(trace, inside, outside=None):
    """Device-idle time inside the spans ``inside`` and outside the spans
    ``outside``, in per cent of the traced window."""
    lo, hi = trace.window()
    where = ps.intervals(spans(trace, inside))
    if not where or not trace.devices:
        return None
    if outside:
        where = xplane.subtract(where, ps.intervals(spans(trace, outside)))
    return 100.0 * ps.overlap_ns(ps.device_idle(trace), where) / (hi - lo)


def idle_ms_per_span(trace, name):
    evs = spans(trace, name)
    if not evs or not trace.devices:
        return None
    idle = ps.overlap_ns(ps.device_idle(trace), ps.intervals(evs))
    return idle / len(evs) / 1e6


def ms_per(trace, name, per):
    """Summed duration of the spans ``name`` over the number of spans
    ``per``, in ms."""
    num, den = spans(trace, name), spans(trace, per)
    if not num or not den:
        return None
    return sum(e.dur for e in num) / len(den) / 1e6


def programs_per(trace, inside, per):
    """Program launches inside the spans ``inside`` over the number of spans
    ``per``."""
    where, den = spans(trace, inside), spans(trace, per)
    launched = ps.launches(trace)
    if not where or not den or not launched:
        return None
    return ps.starts_inside(launched, ps.intervals(where)) / len(den)


def median_stat_ms(trace, name, stat):
    """Median of the microsecond attr ``stat`` of the spans ``name``, in ms."""
    xs = [float(e.stats[stat]) for e in spans(trace, name)
          if stat in e.stats]
    if not xs:
        return None
    print(f"layer_metrics: {stat} of {len(xs)} {name} spans", file=sys.stderr)
    return statistics.median(xs) / 1e3


def median_cycle_ms(trace, name):
    """Median start-to-start distance of consecutive spans ``name``."""
    starts = [e.start for e in spans(trace, name)]
    if len(starts) < 2:
        return None
    return statistics.median(b - a for a, b in zip(starts, starts[1:])) / 1e6
