"""Share of the traced window a device spends inside collective ops while no
other op runs on it, on the worst device."""

from benchmarks.lib import xplane

NAME, UNIT, LAYER, MOVES = ("collective_exposed_pct", "%", "meshes",
                            "train_mfu")


def compute(trace, spans, counters, ctx):
    shares = xplane.worst_collective_pct(trace)
    return shares[1] if shares else None
