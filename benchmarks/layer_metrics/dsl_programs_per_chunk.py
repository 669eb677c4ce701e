"""Device program executions launched inside the program's ``epoch.run`` span
(``perf/epoch_cache.drive_epoch_chunks``: the whole of a ``fit_epochs`` call
but the caller's wait for the history) over the ``epoch.chunk`` spans there.
1 is the chunk program alone; the rest are the eager one-op programs the
driver launches beside it (the key split, the score read). Counted at the
launch, on the host's clock (``lib/program_spans.py``)."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = ("dsl_programs_per_chunk", "count",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    return _program_spans.programs_per(trace, "epoch.run",
                                       per="epoch.chunk")
