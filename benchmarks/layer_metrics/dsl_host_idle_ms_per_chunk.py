"""Device-idle time inside the program's ``epoch.run`` spans over the
``epoch.chunk`` spans there: what the chip waits for the DSL's chunk driver a
chunk (key split, dispatch, listeners, readbacks). The caller's wait for the
loss history lies outside the span and is not in it."""

from benchmarks.layer_metrics import _program_spans
from benchmarks.lib import program_spans as ps

NAME, UNIT, LAYER, MOVES = ("dsl_host_idle_ms_per_chunk", "ms",
                            "DSL training and epoch pipeline", "train_mfu")


def compute(trace, spans, counters, ctx):
    runs = _program_spans.spans(trace, "epoch.run")
    chunks = _program_spans.spans(trace, "epoch.chunk")
    if not runs or not chunks or not trace.devices:
        return None
    idle = ps.overlap_ns(ps.device_idle(trace), ps.intervals(runs))
    return idle / len(chunks) / 1e6
