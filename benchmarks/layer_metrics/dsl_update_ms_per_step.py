"""Device time a step of the DSL's chunk program spends under ``dsl.update``
(gradient normalisation, the updater's math, the parameter update) and
``dsl.cast`` (the compute-dtype copy of the parameters, the gradients' cast
back) together (``_dsl_scopes``: each op once)."""

from benchmarks.layer_metrics import _dsl_scopes

NAME, UNIT, LAYER, MOVES = ("dsl_update_ms_per_step", "ms",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    return _dsl_scopes.of(_dsl_scopes.step_ms(trace, ctx), "dsl.update",
                          "dsl.cast")
