"""Median device time of one execution of the decode program."""

from benchmarks.layer_metrics import _serve

NAME, UNIT, LAYER, MOVES = "decode_step_ms", "ms", "serving", "serve_tpot_p50_ms"


def compute(trace, spans, counters, ctx):
    return _serve.median_decode_ms(trace, counters, ctx)
