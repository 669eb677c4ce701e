"""Device time a training step spends in ops under no scope of the program's
vocabulary (``deeplearning4j_tpu/scopes.py``) that are no Pallas kernel: what
the scope metrics and ``flash_ms_per_step`` leave unexplained. With them it
adds up to the time the step program's ops ran (``_scopes``)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("unscoped_ms_per_step", "ms", "LM training",
                            "train_mfu")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.step_ms(trace, counters, ctx), "unscoped")
