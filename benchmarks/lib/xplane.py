"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

The profiler writes one plane per device (``/device:TPU:<n>``) with a line of
executed XLA ops (``XLA Ops``) and a line of executed programs (``XLA
Modules``), and one plane for the host's threads, where
``jax.profiler.TraceAnnotation`` spans land. All share one clock
(nanoseconds). This module loads those into plain lists (``Trace``) and
computes from them:

- busy time: the union of the intervals in which an op ran on a device;
- self time per op and total time per program;
- idle gaps, each attributed to the benchmark's host span open at the time
  and to the program that ran before the gap;
- time in collective ops and the part of it during which no other op ran.

``Trace.to_recorded`` / ``Trace.from_recorded`` turn a trace into a small
JSON object and back, so a trimmed trace from the chip can sit beside this
file and pin the arithmetic in a test.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)")
# stats worth keeping with an op: where its name came from in the program
KEPT_STATS = ("tf_op", "hlo_category", "long_name", "name", "source",
              "hlo_module", "run_id", "step")

Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start: float            # ns on the profile's clock
    dur: float              # ns
    stats: Dict[str, str] = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class DeviceTrace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[int, DeviceTrace] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)   # bench.* annotations

    @classmethod
    def from_recorded(cls, obj: dict) -> "Trace":
        """A trimmed trace as ``tools/trace_report.py --record`` wrote it."""
        def evs(xs):
            return [Event(n, s, d, dict(st)) for n, s, d, st in xs]

        return cls({int(k): DeviceTrace(evs(d["ops"]), evs(d["modules"]))
                    for k, d in obj["devices"].items()}, evs(obj["host"]))

    # ---- the traced window -------------------------------------------------
    def window(self) -> Interval:
        """The ``bench.trace_window`` annotation where the harness wrote
        one; else first start to last end of anything on a device."""
        for e in self.host:
            if e.name == HOST_PREFIX + "trace_window":
                return (e.start, e.end)
        evs = [e for d in self.devices.values() for e in d.ops + d.modules]
        if not evs:
            return (0.0, 0.0)
        return (min(e.start for e in evs), max(e.end for e in evs))


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = trace.devices.setdefault(int(m.group(1)), DeviceTrace())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend(_events(line, KEPT_STATS))
                elif line.name == MODULES_LINE:
                    dev.modules.extend(_events(line, KEPT_STATS))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host.extend(
                    e for e in _events(line, None, prefix=HOST_PREFIX))
    for dev in trace.devices.values():
        dev.ops.sort(key=lambda e: (e.start, -e.dur))
        dev.modules.sort(key=lambda e: e.start)
    trace.host.sort(key=lambda e: (e.start, -e.dur))
    return trace


def _events(line, keep: Optional[Sequence[str]], prefix: str = ""):
    for ev in line.events:
        name = ev.name
        if prefix and not name.startswith(prefix):
            continue
        stats = {}
        for k, v in ev.stats:
            if keep is None or k in keep:
                stats[k] = str(v)[:300]
        yield Event(name, float(ev.start_ns), float(ev.duration_ns), stats)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by the
    (disjoint, sorted) intervals ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _spans(events: Iterable[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------
def device_busy(trace: Trace) -> Dict[int, float]:
    """Seconds in which some op ran, per device, inside the window."""
    lo, hi = trace.window()
    return {k: total(clip(union(_spans(d.ops or d.modules)), lo, hi)) / 1e9
            for k, d in trace.devices.items()}


def busy_and_window(trace: Trace) -> Tuple[float, float]:
    """``(busy_s averaged over devices, window_s)``."""
    lo, hi = trace.window()
    busy = device_busy(trace)
    mean = sum(busy.values()) / len(busy) if busy else 0.0
    return mean, (hi - lo) / 1e9


def idle_pct(trace: Trace) -> Optional[float]:
    """100 x (1 - busy / window), averaged over devices; ``None`` when the
    trace holds no device op."""
    busy, window = busy_and_window(trace)
    return 100.0 * (1.0 - busy / window) if window > 0 and busy > 0 else None


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event's duration less the part its children cover (an op line
    nests a ``while`` body inside the ``while``). Events sorted by start,
    longer first on ties."""
    out: List[Tuple[Event, float]] = []
    stack: List[int] = []
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and out[stack[-1]][0].end <= e.start:
            stack.pop()
        if stack:
            parent, self_ns = out[stack[-1]]
            overlap = min(e.end, parent.end) - e.start
            out[stack[-1]] = (parent, self_ns - max(0.0, overlap))
        out.append((e, e.dur))
        stack.append(len(out) - 1)
    return out


_HLO = re.compile(r"^%([^\s=]+) = (.*)$", re.S)
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def op_label(e: Event) -> str:
    """A short name that survives renumbering. On a TPU the profiler names
    an op by its whole HLO instruction (``%fusion.13 = bf16[49152,3072]{...}
    fusion(...)``): keep the instruction's name without its number, its
    first result shape, and ``[mosaic]`` for a Pallas kernel. Elsewhere the
    name is the HLO name; drop the number."""
    m = _HLO.match(e.name)
    if not m:
        return re.sub(r"[.\d]+$", "", e.name)
    base = re.sub(r"[.\d]+$", "", m.group(1))
    shape = _SHAPE.search(m.group(2))
    mosaic = " [mosaic]" if 'custom_call_target="tpu_custom_call"' in e.name \
        else ""
    return f"{base} {shape.group(0) if shape else ''}{mosaic}".strip()


def op_name(e: Event) -> str:
    """The HLO instruction's own name (``all-reduce.3``), whichever way the
    profiler spelled the event."""
    m = _HLO.match(e.name)
    return m.group(1) if m else e.name


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """``[[label, seconds], ...]``: self time per op label, summed over the
    window and averaged over devices, largest first."""
    lo, hi = trace.window()
    sums: Dict[str, float] = {}
    for d in trace.devices.values():
        for e, self_ns in self_times(d.ops):
            if lo <= e.start < hi:
                label = op_label(e)
                sums[label] = sums.get(label, 0.0) + self_ns
    k = max(1, len(trace.devices))
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / k] for name, ns in ranked]


def module_times(trace: Trace, by_id: bool = False) -> Dict[str, List[float]]:
    """Device seconds of each execution of each program over all devices,
    inside the window: by program name (``jit_step``), or with ``by_id`` by
    name and the compiler's program number (``jit_step(12)``), which tells
    apart programs that share a name."""
    lo, hi = trace.window()
    out: Dict[str, List[float]] = {}
    for d in trace.devices.values():
        for e in d.modules:
            if lo <= e.start < hi:
                key = e.name.strip() if by_id else module_name(e.name)
                out.setdefault(key, []).append(e.dur / 1e9)
    return out


def module_name(raw: str) -> str:
    return re.sub(r"\(\d+\)$", "", raw).strip()


def ops_matching(trace: Trace, pattern: str) -> List[Event]:
    """Ops whose HLO name or kept stats match ``pattern``, all devices,
    inside the window."""
    lo, hi = trace.window()
    rx = re.compile(pattern)
    return [e for d in trace.devices.values() for e in d.ops
            if lo <= e.start < hi and (
                rx.search(e.name)
                or any(rx.search(v) for v in e.stats.values()))]


def idle_gaps(trace: Trace, device: Optional[int] = None,
              min_ns: float = 1000.0) -> List[Tuple[float, float, str]]:
    """``[(start, end, label)]`` for every idle gap on one device (the
    lowest-numbered by default) inside the window. The label is the
    innermost ``bench.*`` host span open at the gap's midpoint and the
    program that ran before the gap."""
    if not trace.devices:
        return []
    device = min(trace.devices) if device is None else device
    d = trace.devices[device]
    lo, hi = trace.window()
    busy = clip(union(_spans(d.ops or d.modules)), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    mods = d.modules
    out = []
    mi = 0
    for a, b in gaps:
        if b - a < min_ns:
            continue
        mid = (a + b) / 2
        host = "no-span"
        for e in trace.host:        # sorted by start: last match is innermost
            if e.start > mid:
                break
            if e.end >= mid and e.name != HOST_PREFIX + "trace_window":
                host = e.name[len(HOST_PREFIX):]
        while mi + 1 < len(mods) and mods[mi + 1].start <= a:
            mi += 1
        prev = (module_name(mods[mi].name)
                if mods and mods[mi].start <= a else "start")
        out.append((a, b, f"{host} after {prev}"))
    return out


def top_idle_gaps(trace: Trace, n: int = 10,
                  min_ns: float = 1000.0) -> List[List]:
    """``[[label, seconds], ...]``: idle time on the first device summed by
    label, largest first; gaps under ``min_ns`` are left out."""
    sums: Dict[str, float] = {}
    for a, b, label in idle_gaps(trace, min_ns=min_ns):
        sums[label] = sums.get(label, 0.0) + (b - a)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def collective_seconds(trace: Trace) -> Dict[int, Tuple[float, float]]:
    """Per device ``(collective_s, exposed_s)``: time inside collective ops,
    and the part of it during which no other leaf op ran on that device."""
    lo, hi = trace.window()
    out = {}
    for k, d in trace.devices.items():
        timed = self_times(d.ops)
        coll = union(clip(_spans(e for e, _ in timed
                                 if COLLECTIVE.match(op_name(e))), lo, hi))
        # leaf compute: ops that are not collectives and that no child op
        # fills (a container's own time is scheduling, not compute)
        leaf = union(clip(_spans(
            e for e, self_ns in timed
            if not COLLECTIVE.match(op_name(e)) and self_ns >= 0.5 * e.dur),
            lo, hi))
        out[k] = (total(coll) / 1e9, total(subtract(coll, leaf)) / 1e9)
    return out


def worst_collective_pct(trace: Trace) -> Optional[Tuple[float, float]]:
    """``(collective, exposed)`` as shares of the window in per cent, each on
    its worst device; ``None`` on fewer than two devices."""
    lo, hi = trace.window()
    if len(trace.devices) < 2 or hi <= lo:
        return None
    per_dev = collective_seconds(trace).values()
    scale = 100.0 / ((hi - lo) / 1e9)
    return (scale * max(c for c, _ in per_dev),
            scale * max(x for _, x in per_dev))
