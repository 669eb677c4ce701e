"""``admit_idle_pct`` above the knee: device-idle time inside the program's
``serve.admit`` spans, as a share of the traced window."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "sat_admit_idle_pct", "%", "serving", "serve_tok_per_s"


def compute(trace, spans, counters, ctx):
    return _program_spans.idle_pct(trace, "serve.admit")
