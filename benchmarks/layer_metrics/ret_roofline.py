"""Share of its roofline the retention-step kernel reaches in a decode step:
the least time the chip could take to move what the kernels must move
(``lib/retention_cost.step_bytes``: the live slots' ``S`` and ``Z`` read once
and written once and the kernel's small operands, every layer, over the peak
bandwidth) divided by the device time under ``ret.step`` in a decode step.
Memory bound: 13 float32 vector operations on each 4 KiB tile moved.

The live slots are **the traced window's own**: the mean ``state_slots`` of
the program's ``serve.decode`` spans in the trace that dispatched (the host's
cursors), not the whole run's mean: the kernel's time follows the slots live
in the steps that were traced, and a numerator from another window has read
``pool_attn_roofline`` and ``moe_dense_roofline`` over 100 % (PERF.md section
7). A value over 100 is a fault in the count, not a result."""

from benchmarks.layer_metrics import _hybrid, _program_spans
from benchmarks.lib import retention_cost

NAME, UNIT, LAYER, MOVES = ("ret_roofline", "%", "linear attention",
                            "serve_tpot_p50_ms")


def traced_live(trace):
    """Mean ``state_slots`` of the ``serve.decode`` spans in the trace that
    dispatched, or ``None``."""
    live = [float(e.stats["state_slots"])
            for e in _program_spans.spans(trace, "serve.decode")
            if float(e.stats.get("state_slots", 0)) > 0]
    return sum(live) / len(live) if live else None


def compute(trace, spans, counters, ctx):
    live = traced_live(trace)
    if not live:
        return None
    return _hybrid.roofline_pct(
        trace, counters, ctx, "ret_step",
        retention_cost.step_bytes(ctx["config"], live=live))
