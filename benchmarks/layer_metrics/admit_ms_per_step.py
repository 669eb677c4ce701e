"""Host time of admission a decode step pays: summed ``serve.admit`` span
durations over the ``serve.decode`` spans in the traced window."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "admit_ms_per_step", "ms", "serving", "serve_tpot_p50_ms"


def compute(trace, spans, counters, ctx):
    return _program_spans.ms_per(trace, "serve.admit", per="serve.decode")
