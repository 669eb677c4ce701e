"""Device time in the decode-attention kernel per decode step, in a cell whose
decode program also holds the reached-experts kernel: the Mosaic calls inside
the decode program whose name holds no ``moe.experts`` (``_pool_attn``), over
the decode steps in the trace."""

from benchmarks.layer_metrics import _pool_attn

NAME, UNIT, LAYER, MOVES = ("pool_attn_ms_per_decode_step", "ms", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _pool_attn.decode_ms(trace, counters, ctx)
