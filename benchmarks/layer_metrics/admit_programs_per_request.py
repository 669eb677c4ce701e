"""Device program executions launched inside the program's ``serve.admit`` spans
over the ``serve.prefill`` spans there: the prefill program and the eager one-op
programs of each admission. Counted at the launch, on the host's clock: the
device's timeline is offset from it by up to a millisecond (``lib/
program_spans.py``)."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = ("admit_programs_per_request", "count", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _program_spans.programs_per(trace, "serve.admit",
                                       per="serve.prefill")
