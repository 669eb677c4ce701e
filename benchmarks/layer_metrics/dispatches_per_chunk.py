"""Train-program launches per ``fit_epochs`` call, from the program's metrics
registry (``train_chunk_dispatches_total``; largest seen in the window). 1 is
the fused path; anything more is a fallback."""

NAME, UNIT, LAYER, MOVES = ("dispatches_per_chunk", "count",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    return counters.get("dispatches_per_chunk")
