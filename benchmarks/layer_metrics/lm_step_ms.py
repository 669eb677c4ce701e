"""Median host time of one ``fit_batch`` step, closed by the loss reaching the
host. In a traced run only the steps before the profiler started count."""

NAME, UNIT, LAYER, MOVES = "lm_step_ms", "ms", "LM training", "train_mfu"


def compute(trace, spans, counters, ctx):
    return counters.get("median_step_ms")
