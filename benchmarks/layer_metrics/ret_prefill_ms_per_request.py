"""Device time an admission spends in the power-retention mixers: ops under
the ``ret.proj`` and ``ret.scan`` scopes (the projections, head norms, RoPE,
gate and the chunked recurrence) inside the prefill programs, over the
program's ``serve.prefill`` spans in the trace. A prefill holds back every
live slot's next token, so this moves TPOT as well as TTFT."""

from benchmarks.layer_metrics import _hybrid, _program_spans

NAME, UNIT, LAYER, MOVES = ("ret_prefill_ms_per_request", "ms",
                            "linear attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    found = _hybrid.scoped(trace, counters, ctx, "ret")
    requests = _program_spans.spans(trace, "serve.prefill")
    if not found or not requests:
        return None
    return 1e3 * found["prefill"][0] / len(requests)
