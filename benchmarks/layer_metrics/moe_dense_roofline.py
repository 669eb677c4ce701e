"""Share of its roofline the routed feed-forward of a model whose experts'
width is ``moe_intermediate_size`` reaches in a decode step: the least time
the chip could take to move what the step must move
(``lib/mixed_attn_cost.routed_step_bytes``: the stored bytes of the experts
the live slots reached, the routers, the rows in and out, over the peak
bandwidth) divided by the device time of the ``moe.*`` scopes in a decode
step (``moe_roofline``'s time; its bytes read ``intermediate_size``, a dense
width this model has no layer for). The experts reached and the live rows are
the window's means from the server's own counts. A value over 100 is a fault
in the count, not a result."""

from benchmarks.layer_metrics import _moe
from benchmarks.lib import mixed_attn_cost, peaks

NAME, UNIT, LAYER, MOVES = ("moe_dense_roofline", "%", "routed experts",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    found = _moe.scoped_seconds(trace, counters, ctx)
    touched = counters.get("moe_experts_touched_per_step")
    if not found or not found["decode"][0] or not touched:
        return None
    seconds, steps = found["decode"]
    nbytes = mixed_attn_cost.routed_step_bytes(
        ctx["config"], tokens=counters["moe_live_slots_per_step"],
        touched=touched)
    least = nbytes / peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
