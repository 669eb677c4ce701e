"""Device time a training step spends in the step's compute-dtype copy of the
parameters and the gradients' cast back to the master dtype: ops under
``opt.cast``;
inside the step program, over the steps in the trace (``_scopes``: each op
once, a Pallas kernel never)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("param_cast_ms_per_step", "ms", "LM training",
                            "train_mfu")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.step_ms(trace, counters, ctx),
                      "opt.cast")
