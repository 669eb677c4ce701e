"""Device-idle time inside the program's ``serve.admit`` spans (key, pad,
prefill dispatch, first-token read-back and slot bookkeeping of each
admission), as a share of the traced window."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "admit_idle_pct", "%", "serving", "serve_tpot_p50_ms"


def compute(trace, spans, counters, ctx):
    return _program_spans.idle_pct(trace, "serve.admit")
