"""Slots that hold a request in a decode step: the mean of the ``live`` attr
over the program's ``serve.decode`` spans of the window that dispatched
(``live`` > 0: the last span of a busy stretch only reads the block before
it). With ``kv_blocks_share`` it says at which occupancy a decode-path number
was read. ``None`` where the trace holds no such span."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = ("live_slots_per_step", "count", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    live = [float(e.stats["live"])
            for e in _program_spans.spans(trace, "serve.decode")
            if float(e.stats.get("live", 0)) > 0]
    return sum(live) / len(live) if live else None
