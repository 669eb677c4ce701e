"""Device time an admission spends in the flash-attention kernels: the Mosaic
calls that start inside an execution of a prefill program (every program of
the cell's ``prefill_program`` but the decode program) and are not the
experts' (``moe.experts``, ``ragged-dot-*``: ``_mixed_attn.kind_of``), over
the program's ``serve.prefill`` spans in the trace: three banded grids and one
full-causal grid a request at the cell's cut, on the rungs of 4,096 positions
and more (a shorter rung attends by the XLA op, ``attn.core``). A prefill holds back every live slot's next token, so this moves TPOT as
well as TTFT. ``None`` where there is nothing to read."""

from benchmarks.layer_metrics import _mixed_attn, _program_spans, _serve

NAME, UNIT, LAYER, MOVES = ("flash_prefill_ms_per_request", "ms",
                            "window and full attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    key = _serve._decode_id(trace, counters, ctx)
    spec = ctx["cell"].get("prefill_program")
    requests = _program_spans.spans(trace, "serve.prefill")
    if key is None or not spec or not requests:
        return None
    keys = set(_serve._runs(trace, spec["module"])) - {key}
    ns, _ = _mixed_attn.mosaic_ns(trace, keys, ("window", "full"))
    return ns / 1e6 / len(requests) if ns else None
