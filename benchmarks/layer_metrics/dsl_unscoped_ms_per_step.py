"""Device time a step of the DSL's chunk program spends in ops under no
``dsl.*`` scope (``unscoped``, and ``mosaic``: no kernel sits under ``nn/``
today): what the other five ``dsl_*_ms_per_step`` metrics leave unexplained.
With them it adds up to the time the chunk program's ops ran. (A name of its
own because ``unscoped_ms_per_step`` reads the LM cells' ``step_program``.) A
gradient all-reduce that GSPMD gave no ``tf_op`` lands here and is also what
``collective_pct`` counts: the two are not to be added."""

from benchmarks.layer_metrics import _dsl_scopes

NAME, UNIT, LAYER, MOVES = ("dsl_unscoped_ms_per_step", "ms",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    return _dsl_scopes.outside(_dsl_scopes.step_ms(trace, ctx))
