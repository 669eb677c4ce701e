"""Share of its roofline the KDA part reaches in a decode step: the least
time the chip could take to move what the step must move
(``lib/hybrid_cost.kda_step_bytes``: the mixers' stored weights, the live
slots' recurrent matrices and convolution tails read and written, the rows in
and out, over the peak bandwidth) divided by the device time of the ``kda.*``
scopes in a decode step. Memory bound: a row's 7 H dk^2 state operations and
its projections are 1.1e8 FLOP a layer against 2.1 MB of state and 210 MB of
weights. The live slots are the window's mean from the server's own counts. A
value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import hybrid_cost

NAME, UNIT, LAYER, MOVES = ("kda_roofline", "%", "linear attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("moe_live_slots_per_step")
    if not live:
        return None
    return _hybrid.roofline_pct(
        trace, counters, ctx, "kda",
        hybrid_cost.kda_step_bytes(ctx["config"], live=live))
