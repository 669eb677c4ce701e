"""Share of its roofline the recurrence reaches in the prefills of the trace:
the least time the chip needs for the recurrence's own work over the prompt
tokens admitted (``lib/gdn_cost.scan_seconds``: per token, layer and value
head ``S'^T k``, the rank-one update and ``S^T q``, 6 dk dv FLOP, and q, k, v,
o and the gates moved once; the larger of FLOP over the peak and bytes over
the bandwidth) divided by the device time under ``gdn.scan`` inside the
prefill programs. The count is of the recurrence, whatever algorithm computes
it: a chunked form's extra products and a state that travels to memory and
back are time the share does not excuse. The prompt tokens are the
``prompt_len`` of the program's ``serve.prefill`` spans in the trace. A value
over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _hybrid, _program_spans
from benchmarks.lib import gdn_cost, peaks

NAME, UNIT, LAYER, MOVES = ("gdn_scan_roofline", "%", "linear attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    found = _hybrid.scoped(trace, counters, ctx, "gdn_scan")
    tokens = sum(float(e.stats.get("prompt_len", 0))
                 for e in _program_spans.spans(trace, "serve.prefill"))
    if not found or not found["prefill"][0] or not tokens:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])
    least = gdn_cost.scan_seconds(
        ctx["config"], tokens=tokens, flops_per_s=peak["bf16_flops"],
        bytes_per_s=peak["hbm_bytes_per_s"])
    return 100.0 * least / found["prefill"][0]
