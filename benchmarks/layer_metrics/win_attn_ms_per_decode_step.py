"""Device time a decode step spends reading the rings of the layers with a
sliding window: the Mosaic calls named ``attn.window`` inside the decode
program, over its executions in the trace, all such layers together
(``_mixed_attn``)."""

from benchmarks.layer_metrics import _mixed_attn

NAME, UNIT, LAYER, MOVES = ("win_attn_ms_per_decode_step", "ms",
                            "window and full attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _mixed_attn.decode_ms(trace, counters, ctx, "window")
