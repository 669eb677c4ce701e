"""95th percentile over requests of (finish - first token) / (tokens - 1) below
the knee. 0.3 % between runs on a quiet host, 2.4 % on a busy one (PERF.md
section 2): no bound fits both, so the median is the bounded one."""

NAME, UNIT, LAYER, MOVES = "serve_tpot_p95_ms", "ms", "serving", "serve_tpot_p50_ms"


def compute(trace, spans, counters, ctx):
    return counters.get("tpot_p95_ms")
