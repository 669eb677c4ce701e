"""1 - (union of the intervals in which an op ran on the device) / traced
window. Serving above the knee, where idle time is the host loop's doing."""

from benchmarks.lib import xplane

NAME, UNIT, LAYER, MOVES = "serve_idle_pct", "%", "device", "serve_tok_per_s"


def compute(trace, spans, counters, ctx):
    return xplane.idle_pct(trace)
