"""Shared by the pool-read metrics of a cell whose decode program holds two
kinds of Pallas kernel: the decode-attention kernel
(``pallas/decode_attention.py``, called with no scope open: its instruction
keeps its own name) and the reached-experts kernel, lowered under
``moe.experts`` and so NAMED by it (``scopes.py``). The pool read is every
Mosaic call that starts inside an execution of the decode program and whose
name holds no ``moe.experts``; ``moe_ms_per_decode_step`` reads the others by
their scope. ``decode_attn_ms_per_step`` would add the two."""

from benchmarks.layer_metrics import _serve

MOSAIC = 'custom_call_target="tpu_custom_call"'
EXPERTS = "moe.experts"


def decode_ms(trace, counters, ctx):
    """ms a decode step spends in the pool-read kernel, all attention layers,
    or ``None`` where there is nothing to read: no trace, a decode program
    that is not told apart, one with no such call (the XLA read)."""
    key = _serve._decode_id(trace, counters, ctx)
    if key is None:
        return None
    lo, hi = trace.window()
    ns, steps = 0.0, 0
    for d in trace.devices.values():
        runs = [(e.start, e.end) for e in d.modules
                if lo <= e.start < hi and e.name.strip() == key]
        steps += len(runs)
        ns += sum(e.dur for e in d.ops
                  if MOSAIC in e.name and EXPERTS not in e.name
                  and any(a <= e.start < b for a, b in runs))
    return ns / 1e6 / steps if ns and steps else None
