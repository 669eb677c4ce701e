"""How unevenly the router spread the window's work: the busiest expert's
routed (token, expert) pairs over its layer's mean, in the worst layer, from
the server's own ``moe_expert_load`` (prefills and decode steps, live rows
only). 1 is even. Seeded weights route nearly evenly; a trained router does
not."""

NAME, UNIT, LAYER, MOVES = ("moe_load_max_over_mean", "ratio",
                            "routed experts", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return counters.get("moe_load_max_over_mean")
