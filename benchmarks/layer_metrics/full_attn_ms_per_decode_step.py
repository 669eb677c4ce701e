"""Device time a decode step spends reading the ``T_max`` pool of the layers
that attend their whole prefix: the Mosaic calls inside the decode program
named neither ``attn.window`` nor ``moe.experts``, over its executions in the
trace (``_mixed_attn``). With ``win_attn_ms_per_decode_step`` it adds up to
the decode program's pool-read time."""

from benchmarks.layer_metrics import _mixed_attn

NAME, UNIT, LAYER, MOVES = ("full_attn_ms_per_decode_step", "ms",
                            "window and full attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _mixed_attn.decode_ms(trace, counters, ctx, "full")
