"""1 - (union of the intervals in which an op ran on the device) / traced
window, averaged over the chips used. Training cells."""

from benchmarks.lib import xplane

NAME, UNIT, LAYER, MOVES = "device_idle_pct", "%", "device", "train_mfu"


def compute(trace, spans, counters, ctx):
    return xplane.idle_pct(trace)
