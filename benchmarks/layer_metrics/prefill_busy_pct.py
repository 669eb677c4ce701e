"""The prefill programs' share of the device's busy time in the traced window.
The serve loop is one thread, so a prefill holds back every live slot's next
token as well as the first token of the requests behind it."""

from benchmarks.layer_metrics import _serve
from benchmarks.lib import xplane

NAME, UNIT, LAYER, MOVES = ("prefill_busy_pct", "%", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    secs = _serve.prefill_seconds(trace, counters, ctx)
    busy = sum(xplane.device_busy(trace).values())
    return 100.0 * sum(secs) / busy if secs and busy else None
