"""Share of its roofline the attention over the selection reaches in a
decode step of a latent layer without a rotary part: the least time the chip
could take to move what the step must move
(``lib/pooled_index_cost.attend_step_bytes``: the selected pools' rows and
the tail, 512 lanes a row as stored, ``wukv`` as stored, the rows, over the
peak bandwidth) divided by the device time of the ``mla.attend`` scope in a
decode step (the gather of the selected rows and the absorbed attention).
Memory bound. A value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import pooled_index_cost

NAME, UNIT, LAYER, MOVES = ("nope_attend_roofline", "%", "sparse attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("moe_live_slots_per_step")
    keys = counters.get("keys_attended_per_step")
    if not live or not keys or "index_kpool" not in ctx["config"]:
        return None
    return _hybrid.roofline_pct(
        trace, counters, ctx, "mla_attend",
        pooled_index_cost.attend_step_bytes(
            ctx["config"], keys_attended=keys, live=live))
