"""Share of its memory roofline the hyper-connections reach in a decode
step: the least time the chip could take to move what they must move
(``lib/hc_cost.step_bytes``: every sub-layer's ``Phi`` as stored, the live
slots' four streams read and written, over the peak bandwidth) divided by
the device time under ``hc.map`` and ``hc.mix`` in a decode step. Low is the
finding: the part is chains of small ops that wait for launches, not for
memory. A value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import hc_cost

NAME, UNIT, LAYER, MOVES = ("hc_roofline", "%", "residual path",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("moe_live_slots_per_step")
    if not live or "hc_mult" not in ctx["config"]:
        return None
    return _hybrid.roofline_pct(
        trace, counters, ctx, "hc",
        hc_cost.step_bytes(ctx["config"], live=live))
