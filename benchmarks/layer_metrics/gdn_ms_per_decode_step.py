"""Device time a decode step spends in the Gated DeltaNet mixers: ops under
the program's ``gdn.proj`` and ``gdn.step`` scopes inside the decode program,
over the decode steps in the trace, all such layers together. ``None`` where
there is nothing to read (``_hybrid``: no trace, a program without the scopes,
a cell without ``gdn_scopes``)."""

from benchmarks.layer_metrics import _hybrid

NAME, UNIT, LAYER, MOVES = ("gdn_ms_per_decode_step", "ms",
                            "linear attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _hybrid.decode_ms(trace, counters, ctx, "gdn")
