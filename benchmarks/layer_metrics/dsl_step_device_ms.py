"""Device time of one optimizer step of the DSL's chunk program: the mean
device time of an execution of ``jit_run`` in the traced window over the steps
an execution runs, which the program's own ``epoch.chunk`` spans say
(``_dsl_scopes.chunk``). The inside counterpart of ``dsl_step_ms``, which is
the harness's clock round the call over the harness's step count."""

from benchmarks.layer_metrics import _dsl_scopes

NAME, UNIT, LAYER, MOVES = ("dsl_step_device_ms", "ms",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    return _dsl_scopes.step_device_ms(trace)
