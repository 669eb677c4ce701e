"""Device time an admission spends selecting and attending: ops under the
``dsa.index`` and ``mla.attend`` scopes (index scores against the slot's keys,
the exact top-k, the gather of the selected rows, the absorbed attention)
inside the prefill programs, over the program's ``serve.prefill`` spans in the
trace. A prefill holds back every live slot's next token, so this moves TPOT
as well as TTFT."""

from benchmarks.layer_metrics import _hybrid, _program_spans

NAME, UNIT, LAYER, MOVES = ("dsa_prefill_ms_per_request", "ms",
                            "sparse attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    found = _hybrid.scoped(trace, counters, ctx, "dsa_prefill")
    requests = _program_spans.spans(trace, "serve.prefill")
    if not found or not requests:
        return None
    return 1e3 * found["prefill"][0] / len(requests)
