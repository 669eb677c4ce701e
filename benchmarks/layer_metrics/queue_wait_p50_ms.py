"""Median wait of a request between ``submit`` and the start of its admission:
the ``queue_wait_us`` attr of the program's ``serve.prefill`` spans in the
traced window (the sample count goes to stderr)."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "queue_wait_p50_ms", "ms", "serving", "serve_tpot_p50_ms"


def compute(trace, spans, counters, ctx):
    return _program_spans.median_stat_ms(trace, "serve.prefill",
                                         "queue_wait_us")
