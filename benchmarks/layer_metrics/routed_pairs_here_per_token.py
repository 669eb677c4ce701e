"""(token, expert) pairs that landed on an expert held here, per live row and
expert layer, over the window (prefills and decode steps), from the server's
own ``moe_expert_load`` and ``moe_rows``. The router chooses 8 of 512 and
this chip holds 64, so the expectation is 8 x 64 / 512 = 1.0; the deployment's
experts would see 8 times the rows (eight chips' slots route to them). Well
off 1.0, the router's groups or the share's first index are wrong."""

NAME, UNIT, LAYER, MOVES = ("routed_pairs_here_per_token", "ratio",
                            "routed experts", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return counters.get("routed_pairs_here_per_token")
