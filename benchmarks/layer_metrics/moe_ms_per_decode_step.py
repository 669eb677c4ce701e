"""Device time a decode step spends in the routed experts: ops under the
program's ``moe.route`` and ``moe.experts`` scopes inside the decode program,
over the decode steps in the trace, all layers together."""

from benchmarks.layer_metrics import _moe

NAME, UNIT, LAYER, MOVES = ("moe_ms_per_decode_step", "ms", "routed experts",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    found = _moe.scoped_seconds(trace, counters, ctx)
    if not found or not found["decode"][1]:
        return None
    seconds, steps = found["decode"]
    return 1e3 * seconds / steps
