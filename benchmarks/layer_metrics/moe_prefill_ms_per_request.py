"""Device time an admission spends in the routed experts: ops under the
``moe.*`` scopes inside the prefill programs, over the program's
``serve.prefill`` spans in the trace. A prefill holds back every live slot's
next token, so this moves TPOT as well as TTFT."""

from benchmarks.layer_metrics import _moe, _program_spans

NAME, UNIT, LAYER, MOVES = ("moe_prefill_ms_per_request", "ms",
                            "routed experts", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    found = _moe.scoped_seconds(trace, counters, ctx)
    requests = _program_spans.spans(trace, "serve.prefill")
    if not found or not requests:
        return None
    return 1e3 * found["prefill"][0] / len(requests)
