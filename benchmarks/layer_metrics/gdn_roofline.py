"""Share of its roofline the Gated DeltaNet part reaches in a decode step: the
least time the chip could take to move what the step must move
(``lib/gdn_cost.gdn_step_bytes``: the mixers' weights as stored, the live
slots' recurrent matrices and convolution tails read and written, the rows in
and out, over the peak bandwidth) divided by the device time of the ``gdn.*``
scopes in a decode step. Memory bound: a row's 7 Hv dk dv state operations
and its projections are 7e7 FLOP a layer against 2.1 MB of state and 135 MB
of weights. The live slots are the window's mean of the program's own
``state_slots`` (slots whose state a step moved, from the host's cursors). A
value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import gdn_cost

NAME, UNIT, LAYER, MOVES = ("gdn_roofline", "%", "linear attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("state_slots_per_step")
    if not live or not ctx["cell"].get("gdn_scopes"):
        return None
    return _hybrid.roofline_pct(
        trace, counters, ctx, "gdn",
        gdn_cost.gdn_step_bytes(ctx["config"], live=live))
