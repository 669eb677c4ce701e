"""Device-idle time inside the program's ``train.step`` spans (``fit_batch``:
dispatch and the wait for the loss) per such span in the traced window."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = "lm_step_host_idle_ms", "ms", "LM training", "train_mfu"


def compute(trace, spans, counters, ctx):
    return _program_spans.idle_ms_per_span(trace, "train.step")
