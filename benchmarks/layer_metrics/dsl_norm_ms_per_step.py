"""Device time a step of the DSL's chunk program spends under ``dsl.norm``
(``BatchNormImpl``, ``LRNImpl``: statistics, running averages, normalisation,
scale and shift, activation), forward and backward (``_dsl_scopes``: each op
once)."""

from benchmarks.layer_metrics import _dsl_scopes

NAME, UNIT, LAYER, MOVES = ("dsl_norm_ms_per_step", "ms",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    return _dsl_scopes.of(_dsl_scopes.step_ms(trace, ctx), "dsl.norm")
