"""Device time a decode step spends in the hyper-connections: ops under the
program's ``hc.map`` (the streams' norm, the projection onto the three maps,
sigmoids, the Sinkhorn sweeps) and ``hc.mix`` (a sub-layer's input and its
write-back, the entry's copies and the exit's sum) scopes inside the decode
program, self time, over the decode steps in the trace, all ten sub-layers
together. ``None`` where there is nothing to read: no trace, a program
without the scopes (the parent of PR 51), a cell without ``hc_scopes``."""

from benchmarks.layer_metrics import _hybrid

NAME, UNIT, LAYER, MOVES = ("hc_ms_per_decode_step", "ms", "residual path",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _hybrid.decode_ms(trace, counters, ctx, "hc")
