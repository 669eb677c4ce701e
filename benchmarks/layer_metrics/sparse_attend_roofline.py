"""Share of its roofline the attention over the selection reaches in a decode
step: the least time the chip could take to move what the step must move
(``lib/sparse_cost.attend_step_bytes``: min(cursor + 1, index_topk) stored
latent rows a live slot and layer, ``wukv`` as stored, the rows, over the peak
bandwidth) divided by the device time of the ``mla.attend`` scope in a decode
step (the gather of the selected rows and the absorbed attention). Memory
bound (``sparse_cost``). A value over 100 is a fault in the count, not a
result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import sparse_cost

NAME, UNIT, LAYER, MOVES = ("sparse_attend_roofline", "%", "sparse attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("moe_live_slots_per_step")
    keys = counters.get("keys_attended_per_step")
    if not live or not keys:
        return None
    return _hybrid.roofline_pct(
        trace, counters, ctx, "mla_attend",
        sparse_cost.attend_step_bytes(ctx["config"], keys_attended=keys,
                                      live=live))
