"""Share of its roofline the latent attention reaches in a speculative round:
the least time the chip could take for what the round's ``mla.attend`` part
must do (``lib/mtp_cost.verify_attend_floor_s``: two queries a live slot
against the slot's rows up to its cursor, in the kept layers and the module's
block; the larger of its FLOP over the peak rate and its bytes over the peak
bandwidth) divided by the device time of the ``mla.attend`` scope in a round.
The cursor and the live slots are the window's means from the server's own
counts. A value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _hybrid
from benchmarks.lib import mtp_cost, peaks

NAME, UNIT, LAYER, MOVES = ("mla_verify_roofline", "%", "latent attention",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = counters.get("moe_live_slots_per_step")
    context = counters.get("decode_context_mean")
    if not live or not context or not counters.get("spec_rounds"):
        return None
    ms = _hybrid.decode_ms(trace, counters, ctx, "mla_attend")
    if not ms:
        return None
    least = mtp_cost.verify_attend_floor_s(
        ctx["config"], peaks.peaks_for(ctx["device_kind"]),
        context=context, live=live)
    return 100.0 * least / (ms / 1e3)
