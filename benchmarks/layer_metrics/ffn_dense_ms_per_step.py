"""Device time a training step spends in the dense feed-forwards, forward and
backward: ops under the program's ``ffn.dense`` scope;
inside the step program, over the steps in the trace (``_scopes``: each op
once, a Pallas kernel never)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("ffn_dense_ms_per_step", "ms", "LM training",
                            "train_mfu")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.step_ms(trace, counters, ctx),
                      "ffn.dense")
