"""How late the load generator ran: 95th percentile of submit instant minus
due instant. The generator is one thread with the server, because the server
takes requests only between steps: this wait for the step in progress is part
of every TTFT, as it would be behind any front end, and is about one step with
its prefills. Well above that, the generator itself was starved."""

NAME, UNIT, LAYER, MOVES = ("gen_late_p95_ms", "ms", "load generator",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return counters.get("gen_late_p95_ms")
