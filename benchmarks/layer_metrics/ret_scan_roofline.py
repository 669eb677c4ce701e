"""Share of the MXU's peak the chunked recurrence reaches in the prefills of
the trace: the least time the chip needs for the recurrence's operations over
the prompt tokens admitted (``lib/retention_cost.scan_seconds``: per token
and layer the ``[1, D] x [D, d]`` products with the state, by the ``D``
stored, the normaliser's, and the masked products inside a chunk of the
cell's ``ret_chunk`` positions, over ``lib/peaks.py``'s bf16 peak) divided by
the device time under ``ret.scan`` inside the prefill programs. Building
``phi`` of a chunk and a rung's pad tail are time the share does not excuse.
The prompt tokens are the ``prompt_len`` of the program's ``serve.prefill``
spans in the trace. A value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _hybrid, _program_spans
from benchmarks.lib import peaks, retention_cost

NAME, UNIT, LAYER, MOVES = ("ret_scan_roofline", "%", "linear attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    found = _hybrid.scoped(trace, counters, ctx, "ret_scan")
    tokens = sum(float(e.stats.get("prompt_len", 0))
                 for e in _program_spans.spans(trace, "serve.prefill"))
    chunk = ctx["cell"].get("ret_chunk")
    if not found or not found["prefill"][0] or not tokens or not chunk:
        return None
    least = retention_cost.scan_seconds(
        ctx["config"], tokens=tokens, chunk=int(chunk),
        flops_per_s=peaks.peaks_for(ctx["device_kind"])["bf16_flops"])
    return 100.0 * least / found["prefill"][0]
