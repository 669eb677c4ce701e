"""Device time a training step spends in the per-leaf Adam update: ops under
``opt.update`` (with whatever XLA fuses into them: PERF.md section 5);
inside the step program, over the steps in the trace (``_scopes``: each op
once, a Pallas kernel never)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("opt_update_ms_per_step", "ms", "LM training",
                            "train_mfu")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.step_ms(trace, counters, ctx),
                      "opt.update")
