"""The program's spans in one trace, by hand: for each ``dl4j.*`` span name its
count, host time, the device-idle time inside it and the device programs
launched inside it -- each span charged only with what its children do not
cover -- the idle time outside every span, and by how much the device's
timeline lay before the host's (``lib/program_spans.device_lead_ns``; the idle
times are after moving it by that). Works on any ``jax.profiler`` trace of the
program (``ProfilerIterationListener``, ``jax.profiler.start_trace``), not only
the harness's.

    python3 benchmarks/tools/span_report.py <file.xplane.pb | trace dir>
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import program_spans as ps  # noqa: E402
from benchmarks.lib import xplane  # noqa: E402


def report(trace, events, launched=()):
    """``[(name, count, host_ms, self_idle_ms, self_launches)]`` in order of
    idle time, and the idle ms outside every span, for ``events`` sorted by
    start, longer first. 'Self' leaves out what lies inside a nested span."""
    idle = ps.device_idle(trace)
    by_name = {}
    for i, e in enumerate(events):      # nesting, by containment in time
        inner = ps.intervals(c for c in events[i + 1:]
                             if c.start >= e.start and c.end <= e.end)
        own = xplane.subtract([(e.start, e.end)], inner)
        row = by_name.setdefault(e.name, [0, 0.0, 0.0, 0])
        row[0] += 1
        row[1] += e.dur / 1e6
        row[2] += ps.overlap_ns(idle, own) / 1e6
        row[3] += ps.starts_inside(launched, own)
    outside = xplane.total(xplane.subtract(idle, ps.intervals(events))) / 1e6
    rows = sorted(((n, *r) for n, r in by_name.items()), key=lambda r: -r[3])
    return rows, outside


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = args[0] if args[0].endswith(".pb") else xplane.find_xplane(args[0])
    if not path:
        print(f"no *.xplane.pb under {args[0]}", file=sys.stderr)
        return 1
    trace = xplane.load_xplane(path)
    # the loader keeps the bench.* host events only
    trace.host = sorted(ps.read_host_events(path),
                        key=lambda e: (e.start, -e.dur))
    events, launched = ps.load(trace), ps.launches(trace)
    lo, hi = trace.window()
    busy, window = xplane.busy_and_window(trace)
    window_ms = (hi - lo) / 1e6
    print(f"window_ms={window_ms:.3f} device_idle_ms="
          f"{xplane.total(ps.device_idle(trace)) / 1e6:.3f} "
          f"busy_s={busy:.6f} window_s={window:.6f} spans={len(events)}")
    print(f"device timeline moved by {ps.device_lead_ns(trace) / 1e3:.1f} us "
          f"({len(launched)} launches)")
    rows, outside = report(trace, events, launched)
    print("span: count, host ms, idle ms inside (not in a child), "
          "% of window, programs launched inside (not in a child)")
    for name, count, host_ms, idle_ms, programs in rows:
        print(f"  {name}: {count}, {host_ms:.3f}, {idle_ms:.3f}, "
              f"{100 * idle_ms / window_ms if window_ms else 0:.3f}, "
              f"{programs}")
    print(f"  (outside every span): -, -, {outside:.3f}, "
          f"{100 * outside / window_ms if window_ms else 0:.3f}, -")
    return 0


if __name__ == "__main__":
    sys.exit(main())
