"""Device time a step of the DSL's chunk program spends in the layers that
are neither convolutions nor norms, and in the loss: ``dsl.pool``,
``dsl.dense``, ``dsl.embed``, ``dsl.recurrent``, ``dsl.act``, ``dsl.vertex``
(a residual add), ``dsl.layer`` and ``dsl.loss`` together, forward and
backward (``_dsl_scopes``: each op once)."""

from benchmarks.layer_metrics import _dsl_scopes

NAME, UNIT, LAYER, MOVES = ("dsl_other_layers_ms_per_step", "ms",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    return _dsl_scopes.of(_dsl_scopes.step_ms(trace, ctx),
                          *_dsl_scopes.LAYERS)
