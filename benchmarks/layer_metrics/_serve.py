"""Shared by the serving metrics: device seconds of the decode and prefill
programs' executions in the traced window.

The engine jits ``functools.partial`` objects, so every serving program is
called ``jit__unknown`` in the trace; only the compiler's program number tells
them apart. The server dispatches its decode program once per ``steps`` count,
so the workload file says ``"decode_program": {"module": <regex>, "runs":
<counter>}``: decode is the one program matching ``module`` whose executions
in the trace number what the driver counted under the profiler (``runs`` names
that counter; the profiler's edges may cost an execution or two). None or more
than one such program: the reader says so on stderr and returns nothing,
rather than guess. ``"prefill_program": {"module": <regex>, "except":
"decode_program"}`` is every other program matching ``module``. (Naming the
programs is the ``tracing`` issue's.)
"""

import re
import statistics
import sys

from benchmarks.lib import xplane

EDGE = 2      # executions the profiler's start and stop may add or lose


def _runs(trace, module):
    rx = re.compile(module)
    return {k: v for k, v in xplane.module_times(trace, by_id=True).items()
            if rx.search(xplane.module_name(k))}


def _decode_id(trace, counters, ctx):
    spec = ctx["cell"].get("decode_program")
    want = counters.get(spec["runs"]) if spec else None
    if want is None or not trace.devices:
        return None
    near = [k for k, v in _runs(trace, spec["module"]).items()
            if abs(len(v) - want) <= EDGE]
    if len(near) != 1:
        print(f"layer_metrics: {len(near)} programs matching "
              f"{spec['module']!r} ran {want}+-{EDGE} times in the trace; "
              "the decode program is not told apart", file=sys.stderr)
        return None
    return near[0]


def decode_seconds(trace, counters, ctx):
    k = _decode_id(trace, counters, ctx)
    return [] if k is None else xplane.module_times(trace, by_id=True)[k]


def prefill_seconds(trace, counters, ctx):
    spec = ctx["cell"].get("prefill_program")
    k = _decode_id(trace, counters, ctx)
    if not spec or k is None:
        return []
    return [s for m, v in _runs(trace, spec["module"]).items() if m != k
            for s in v]


def median_decode_ms(trace, counters, ctx):
    secs = decode_seconds(trace, counters, ctx)
    return 1e3 * statistics.median(secs) if secs else None
