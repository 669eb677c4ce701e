"""Device time a decode step spends in the KDA mixers: ops under the
program's ``kda.proj`` and ``kda.step`` scopes inside the decode program,
over the decode steps in the trace, all KDA layers together."""

from benchmarks.layer_metrics import _hybrid

NAME, UNIT, LAYER, MOVES = ("kda_ms_per_decode_step", "ms",
                            "linear attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _hybrid.decode_ms(trace, counters, ctx, "kda")
