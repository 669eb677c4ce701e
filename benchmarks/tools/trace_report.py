"""Look at one trace by hand: planes, lines, the longest ops with their stats,
programs, idle gaps, collectives -- and optionally save a trimmed recording
for ``tests/test_xplane.py``.

    python3 benchmarks/tools/trace_report.py <file.xplane.pb> [--record out.json.gz --from-ms 0 --ms 900]
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import xplane  # noqa: E402


def raw_structure(path, limit=3):
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines[:40]:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:limit]:
                stats = {k: str(v)[:120] for k, v in e.stats}
                print(f"    {e.name!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} {stats}")


def clipped(trace, lo: float, hi: float):
    """Events that overlap ``[lo, hi)``, cut to it."""
    def cut(xs):
        return [xplane.Event(e.name, max(e.start, lo),
                             min(e.end, hi) - max(e.start, lo), dict(e.stats))
                for e in xs if e.end > lo and e.start < hi]

    return xplane.Trace({k: xplane.DeviceTrace(cut(d.ops), cut(d.modules))
                         for k, d in trace.devices.items()}, cut(trace.host))


def recorded_form(trace) -> dict:
    """What ``xplane.Trace.from_recorded`` reads."""
    def evs(xs):
        return [[e.name, e.start, e.dur, e.stats] for e in xs]

    return {"devices": {str(k): {"ops": evs(d.ops), "modules": evs(d.modules)}
                        for k, d in trace.devices.items()},
            "host": evs(trace.host)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--raw", action="store_true")
    ap.add_argument("--record", default=None)
    ap.add_argument("--from-ms", type=float, default=0.0)
    ap.add_argument("--ms", type=float, default=1000.0)
    args = ap.parse_args()
    if args.raw:
        raw_structure(args.path)
    trace = xplane.load_xplane(args.path)
    lo, hi = trace.window()
    busy, window = xplane.busy_and_window(trace)
    print(f"devices={sorted(trace.devices)} window_s={window:.6f} "
          f"busy_s={busy:.6f} idle_pct={100 * (1 - busy / window):.3f}"
          if window else "no window")
    for k, d in sorted(trace.devices.items()):
        print(f"device {k}: {len(d.ops)} ops, {len(d.modules)} programs")
    print("programs (name: runs, median ms, total s):")
    for name, secs in sorted(xplane.module_times(trace).items(),
                             key=lambda kv: -sum(kv[1])):
        print(f"  {name}: {len(secs)}, {1e3 * statistics.median(secs):.3f}, "
              f"{sum(secs):.4f}")
    print("top ops by self time (label, s):")
    for name, s in xplane.top_ops(trace, 25):
        print(f"  {s:.5f}  {name}")
    print("idle gaps by label (label, s):")
    for name, s in xplane.top_idle_gaps(trace, 15):
        print(f"  {s:.5f}  {name}")
    print("collectives (device: total s, exposed s):",
          xplane.collective_seconds(trace))
    print("host spans:", sorted({e.name for e in trace.host}))
    if args.record:
        a = lo + args.from_ms * 1e6
        cut = clipped(trace, a, a + args.ms * 1e6)
        name = xplane.HOST_PREFIX + "trace_window"     # the slice is the window
        cut.host = [e for e in cut.host if e.name != name] + [
            xplane.Event(name, a, args.ms * 1e6)]
        cut.host.sort(key=lambda e: (e.start, -e.dur))
        for dev in cut.devices.values():       # keep the recording small
            for e in dev.ops + dev.modules:
                e.stats = {k: v[:160] for k, v in e.stats.items()
                           if k in ("tf_op", "hlo_category", "long_name")}
        with gzip.open(args.record, "wt") as f:
            json.dump(recorded_form(cut), f, separators=(",", ":"))
        print(f"recorded {sum(len(d.ops) for d in cut.devices.values())} ops "
              f"to {args.record} ({os.path.getsize(args.record)} bytes)")


if __name__ == "__main__":
    main()
