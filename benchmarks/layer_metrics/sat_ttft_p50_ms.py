"""Median time from due instant to first token above the knee, over the
requests that got one. The queue grows all through such a run, so this swings
with the smallest change and decides nothing."""

NAME, UNIT, LAYER, MOVES = "sat_ttft_p50_ms", "ms", "serving", "serve_tok_per_s"


def compute(trace, spans, counters, ctx):
    return counters.get("ttft_p50_ms")
