"""Compile requests (cache hits included) that JAX's monitoring reported inside
the measured window. Must be 0: anything else voids the run (``correct`` is
false) because a program was being built while the clock ran."""

NAME, UNIT, LAYER, MOVES = "compiles_in_window", "count", "compile cache", "setup_s"


def compute(trace, spans, counters, ctx):
    return counters.get("compiles_in_window")
