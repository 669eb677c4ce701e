"""Device time a training step spends in the two ends of the stack, forward and
backward: ops under ``lm.head`` (final norm, unembedding, the float32
log-softmax and mean of the loss) and ``lm.embed`` (the token gather and its
scatter-add);
inside the step program, over the steps in the trace (``_scopes``: each op
once, a Pallas kernel never)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("head_ms_per_step", "ms", "LM training",
                            "train_mfu")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.step_ms(trace, counters, ctx),
                      "lm.head", "lm.embed")
