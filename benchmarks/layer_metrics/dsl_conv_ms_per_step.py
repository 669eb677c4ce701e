"""Device time a step of the DSL's chunk program spends under ``dsl.conv``
(``ConvolutionImpl``: the convolution, its bias add and activation; backward,
the weight, data and bias gradients), forward and backward (``_dsl_scopes``:
each op once)."""

from benchmarks.layer_metrics import _dsl_scopes

NAME, UNIT, LAYER, MOVES = ("dsl_conv_ms_per_step", "ms",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    return _dsl_scopes.of(_dsl_scopes.step_ms(trace, ctx), "dsl.conv")
