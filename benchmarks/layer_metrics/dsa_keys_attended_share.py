"""Latent rows the decode steps' queries attended over the rows their slots
held, over the window: the program's own ``keys_attended`` / ``keys_cached`` of
its ``serve.decode`` spans (summed over live slots and 'mla' layers). Both are
counted on the host from its cursors, min(cursor + 1, index_topk) rows a
query: the work each step was handed, index_topk / the mean live context by
construction, not something observed on the device. It says how much of the
cache the mechanism spares at this traffic; that the device attends those rows
and no others is the check's to hold (the selection-off reference fails it)."""

NAME, UNIT, LAYER, MOVES = ("dsa_keys_attended_share", "ratio",
                            "sparse attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return counters.get("dsa_keys_attended_share")
