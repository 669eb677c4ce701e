"""Median start-to-start distance of consecutive ``serve.step`` spans of the
program: one decode step, its admissions and the host's work between."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "sat_step_cycle_ms", "ms", "serving", "serve_tok_per_s"


def compute(trace, spans, counters, ctx):
    return _program_spans.median_cycle_ms(trace, "serve.step")
