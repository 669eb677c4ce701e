"""Share of its roofline this chip's share of the routed experts reaches in
a decode step: the least time the chip could take to move what the step must
move (``lib/hybrid_cost.routed_step_bytes``: the stored bytes of the held
experts the live slots reached, the routers over all 512 outputs, the shared
experts, the rows in and out, over the peak bandwidth) divided by the device
time of the ``moe.*`` scopes in a decode step. It is to this configuration
what ``moe_roofline`` is to OLMoE's, whose cost function reads that model's
key names. Memory bound. The experts reached and the live rows are the
window's means from the server's own counts. A value over 100 is a fault in
the count, not a result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import hybrid_cost

NAME, UNIT, LAYER, MOVES = ("routed_share_roofline", "%", "routed experts",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("moe_live_slots_per_step")
    touched = counters.get("moe_experts_touched_per_step")
    if not live or not touched:
        return None
    return _hybrid.roofline_pct(
        trace, counters, ctx, "routed",
        hybrid_cost.routed_step_bytes(ctx["config"], live=live,
                                      touched=touched))
