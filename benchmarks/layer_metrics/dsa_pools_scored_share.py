"""Pools the decode steps' queries scored over the positions their slots
held up to their cursors, over the window: the program's own
``pools_scored`` / ``keys_cached`` of its ``serve.decode`` spans (summed
over live slots and latent layers). Both are counted on the host from its
cursors, ``cursor // index_kpool`` pools a query: about a quarter where the
index cache really is compressed, 1 where a key stands for a position. That
the device scores those pools and no others is the check's to hold."""

NAME, UNIT, LAYER, MOVES = ("dsa_pools_scored_share", "ratio",
                            "sparse attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return counters.get("dsa_pools_scored_share")
