"""Device-idle time inside the program's ``serve.step`` spans and outside its
``serve.admit`` spans (deadline sweep, decode dispatch and read-back, the
per-slot token loop), as a share of the traced window."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "step_host_idle_pct", "%", "serving", "serve_tpot_p50_ms"


def compute(trace, spans, counters, ctx):
    return _program_spans.idle_pct(trace, "serve.step", outside="serve.admit")
