"""Host time of one fused ``fit_epochs`` call over the optimizer steps in it
(median call; in a traced run only calls before the profiler started)."""

NAME, UNIT, LAYER, MOVES = ("dsl_step_ms", "ms",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    if "median_chunk_ms" not in counters:
        return None
    return counters["median_chunk_ms"] / counters["steps_per_chunk"]
