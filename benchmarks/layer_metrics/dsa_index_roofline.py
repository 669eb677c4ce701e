"""Share of its roofline the indexer reaches in a decode step: the least time
the chip could take to move what the step must move
(``lib/sparse_cost.index_step_bytes``: the live slots' cached index keys below
their cursors in the layers with an indexer, the indexer weights as stored,
the rows, over the peak bandwidth) divided by the device time of the
``dsa.index`` scope in a decode step. Memory bound (``sparse_cost``). The keys
and the live slots are the window's means from the server's own counts. A
value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import sparse_cost

NAME, UNIT, LAYER, MOVES = ("dsa_index_roofline", "%", "sparse attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("moe_live_slots_per_step")
    keys = counters.get("keys_cached_per_step")
    if not live or not keys:
        return None
    return _hybrid.roofline_pct(
        trace, counters, ctx, "dsa_index",
        sparse_cost.index_step_bytes(ctx["config"], keys_cached=keys,
                                     live=live))
