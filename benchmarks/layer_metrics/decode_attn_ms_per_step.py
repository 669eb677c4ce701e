"""Device time in the decode attention kernel (``pallas/decode_attention.py``)
per decode step: the durations of the Mosaic custom calls that start inside an
execution of the decode program, over those executions. It is to the pool read
what ``flash_ms_per_step`` is to the training kernels. Which program is decode
is ``_serve``'s answer (the one whose runs in the trace number the server's
steps); the prefill programs' flash kernels are Mosaic calls too and lie
outside it. ``None`` where there is nothing to read: no trace, a decode
program that is not told apart, a decode program with no Mosaic call in it (the
XLA read)."""

from benchmarks.layer_metrics import _serve

NAME, UNIT, LAYER, MOVES = ("decode_attn_ms_per_step", "ms", "serving",
                            "serve_tpot_p50_ms")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def compute(trace, spans, counters, ctx):
    key = _serve._decode_id(trace, counters, ctx)
    if key is None:
        return None
    lo, hi = trace.window()
    ns, steps = 0.0, 0
    for d in trace.devices.values():
        runs = [(e.start, e.end) for e in d.modules
                if lo <= e.start < hi and e.name.strip() == key]
        steps += len(runs)
        ns += sum(e.dur for e in d.ops if MOSAIC in e.name
                  and any(a <= e.start < b for a, b in runs))
    return ns / 1e6 / steps if ns and steps else None
