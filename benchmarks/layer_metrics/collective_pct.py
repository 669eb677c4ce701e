"""Share of the traced window a device spends inside collective ops, hidden or
not, on the worst device."""

from benchmarks.lib import xplane

NAME, UNIT, LAYER, MOVES = "collective_pct", "%", "meshes", "train_mfu"


def compute(trace, spans, counters, ctx):
    shares = xplane.worst_collective_pct(trace)
    return shares[0] if shares else None
