"""Share of its roofline the window layers' ring read reaches in a decode
step: the least time the chip could take to read the K and V rows the live
slots hold there (``min(c + 1, sliding_window)`` a layer: the mean of the
program's own ``kv_rows_window`` over the ``serve.decode`` spans in the trace,
times 2 KiB a row) over the device time
of the kernels named ``attn.window`` (``_mixed_attn``). The kernel fetches
whole blocks of 512 positions, so a ring that is not yet full is read past
its cursor: time the share does not excuse. A value over 100 is a fault in
the count, not a result."""

from benchmarks.layer_metrics import _mixed_attn

NAME, UNIT, LAYER, MOVES = ("win_attn_roofline", "%",
                            "window and full attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _mixed_attn.roofline_pct(trace, counters, ctx, "window")
