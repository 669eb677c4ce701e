"""Share of its roofline the pooled indexer reaches in a decode step: the
least time the chip could take for what the step must move and compute
(``lib/pooled_index_cost``: the pooled keys the live slots' queries score,
the indexer weights as stored, the rows; the scores' and projections'
operations; the larger of bytes over peak bandwidth and operations over peak
FLOP/s) divided by the device time under ``dsa.index`` and ``dsa.pool`` in a
decode step. The pools and the live slots are the window's means from the
server's own counts. (``dsa_index_roofline`` counts a key a position and is
not given a cell with pooled keys.) A value over 100 is a fault in the
count, not a result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import peaks, pooled_index_cost

NAME, UNIT, LAYER, MOVES = ("pooled_index_roofline", "%", "sparse attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("moe_live_slots_per_step")
    pools = counters.get("pools_scored_per_step")
    if not live or not pools:
        return None
    ms = _hybrid.decode_ms(trace, counters, ctx, "dsa_index")
    if not ms:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])
    least = max(
        pooled_index_cost.index_step_bytes(
            ctx["config"], pools_scored=pools, live=live)
        / peak["hbm_bytes_per_s"],
        pooled_index_cost.index_step_flops(
            ctx["config"], pools_scored=pools, live=live)
        / peak["bf16_flops"])
    return 100.0 * least / (ms / 1e3)
