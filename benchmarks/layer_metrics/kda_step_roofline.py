"""Share of its memory roofline the KDA layers reach in a decode step: the
least time the chip could take to move what the step must move
(``lib/kda_cost.step_bytes``: the mixers' weights as stored, with the decay
gate and the output gate through their rank, the live slots' float32
recurrent matrices and convolution tails read and written, the rows, over the
peak bandwidth) divided by the device time under ``kda.*`` in a decode step.
(``kda_roofline`` counts Ling's mixer from Ling's key names and is not given
a cell of this configuration.) What the step need not move, and the program
may: the matrices of slots that owe no token. The live slots are the window's
mean from the server's own counts. A value over 100 is a fault in the count,
not a result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import kda_cost

NAME, UNIT, LAYER, MOVES = ("kda_step_roofline", "%", "linear attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("moe_live_slots_per_step")
    if not live or "assumed_sizes" not in ctx["config"]:
        return None
    return _hybrid.roofline_pct(trace, counters, ctx, "kda",
                                kda_cost.step_bytes(ctx["config"], live=live))
