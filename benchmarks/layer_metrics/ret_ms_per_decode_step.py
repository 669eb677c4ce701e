"""Device time a decode step spends in the power-retention mixers: ops under
the program's ``ret.proj`` and ``ret.step`` scopes (the step kernel among
them: the scope names it) inside the decode program, over the decode steps in
the trace, all layers together. ``None`` where there is nothing to read
(``_hybrid``: no trace, a program without the scopes, a cell without
``ret_scopes``)."""

from benchmarks.layer_metrics import _hybrid

NAME, UNIT, LAYER, MOVES = ("ret_ms_per_decode_step", "ms",
                            "linear attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _hybrid.decode_ms(trace, counters, ctx, "ret")
