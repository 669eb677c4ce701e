"""Share of its roofline the full layers' pool read reaches in a decode step:
the least time the chip could take to read the K and V rows the live slots
hold up to their cursors (the mean of the program's own ``kv_rows_full`` over
the ``serve.decode`` spans in the trace, times 2 KiB a row) over the device
time of the pool-read kernels that carry no scope's name (``_mixed_attn``). A
slot's last block is read past its cursor: time the share does not excuse. A
value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _mixed_attn

NAME, UNIT, LAYER, MOVES = ("full_attn_roofline", "%",
                            "window and full attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _mixed_attn.roofline_pct(trace, counters, ctx, "full")
