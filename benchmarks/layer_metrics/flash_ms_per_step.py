"""Device time in the flash attention kernels (forward, dk/dv, dq) per training
step: the sum of their events' durations in the traced window over the number
of step programs that ran in it."""

from benchmarks.layer_metrics import _flash

NAME, UNIT, LAYER, MOVES = ("flash_ms_per_step", "ms", "attention kernels",
                            "train_mfu")


def compute(trace, spans, counters, ctx):
    evs = _flash.kernel_events(trace, ctx)
    steps = _flash.steps_in_trace(trace, ctx)
    if not evs or not steps or not any(evs.values()):
        return None
    return sum(e.dur for v in evs.values() for e in v) / 1e6 / steps
