"""Device time a step of the DSL's chunk program spends under ``dsl.data``:
the epoch's permutation and key splits, and the gather of a batch from the
resident stacks (``_dsl_scopes``: each op once)."""

from benchmarks.layer_metrics import _dsl_scopes

NAME, UNIT, LAYER, MOVES = ("dsl_data_ms_per_step", "ms",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    return _dsl_scopes.of(_dsl_scopes.step_ms(trace, ctx), "dsl.data")
