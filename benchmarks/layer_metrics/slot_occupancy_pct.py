"""Mean live slots over slots per decode dispatch, from the server's own
``slot_dispatches`` and ``steps`` counters."""

NAME, UNIT, LAYER, MOVES = ("slot_occupancy_pct", "%", "serving",
                            "serve_tok_per_s")


def compute(trace, spans, counters, ctx):
    return counters.get("slot_occupancy_pct")
