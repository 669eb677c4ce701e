"""95th percentile of the time from due instant to first token below the knee;
a failed or unfinished request counts as +inf. Swings by 8-11 % between runs
of one code on a busy host (PERF.md section 2): it decides nothing here."""

NAME, UNIT, LAYER, MOVES = "serve_ttft_p95_ms", "ms", "serving", "serve_tpot_p50_ms"


def compute(trace, spans, counters, ctx):
    return counters.get("ttft_p95_ms")
