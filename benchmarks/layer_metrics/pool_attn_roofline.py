"""Share of its roofline the pool read reaches in a decode step: the least
time the chip could take to read the K and V rows the live slots hold up to
their cursors (the window's mean of the program's own ``kv_rows``, from the
host's cursors, times ``lib/gdn_cost.kv_row_bytes``: K and V x kv heads x head
size x the pool's 2 bytes) over the device time of the pool-read kernel in a
decode step (``_pool_attn``). The kernel fetches whole blocks of 512
positions, so a slot's last block is read past its cursor: time the share does
not excuse. A value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _pool_attn
from benchmarks.lib import gdn_cost, peaks

NAME, UNIT, LAYER, MOVES = ("pool_attn_roofline", "%", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    rows = counters.get("kv_rows_per_step")
    ms = _pool_attn.decode_ms(trace, counters, ctx)
    if not rows or not ms:
        return None
    least = rows * gdn_cost.kv_row_bytes(ctx["config"]) / peaks.peaks_for(
        ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)
