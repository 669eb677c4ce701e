"""Median device time of one execution of the decode program, in the cells
whose end-to-end metric is completed tokens per second."""

from benchmarks.layer_metrics import _serve

NAME, UNIT, LAYER, MOVES = ("sat_decode_step_ms", "ms", "serving",
                            "serve_tok_per_s")


def compute(trace, spans, counters, ctx):
    return _serve.median_decode_ms(trace, counters, ctx)
