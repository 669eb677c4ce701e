"""Median time from due instant to first token below the knee. Over ~530
requests at this load it swings by 3-4 % between runs of one code (PERF.md
section 2), which the largest bound allowed cannot hold, so it decides
nothing here."""

NAME, UNIT, LAYER, MOVES = "serve_ttft_p50_ms", "ms", "serving", "serve_tpot_p50_ms"


def compute(trace, spans, counters, ctx):
    return counters.get("ttft_p50_ms")
