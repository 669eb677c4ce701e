"""Shared by the metrics of a cell whose decode program reads two K/V pools
(``mellum2-serve-mixed``: a ring of rows for the layers with a sliding window
beside ``T_max`` rows for the layers without): the decode-attention kernel's
calls told apart by name. The program opens the scope ``attn.window`` round a
window layer's call, which names the Mosaic instruction
(``deeplearning4j_tpu/scopes.py``); a full layer's call keeps its own name,
the reached-experts kernel is named by ``moe.experts`` and a prefill's grouped
expert matmuls ``ragged-dot-*``. So of the Mosaic
calls that start inside an execution of the decode program, those whose name
holds ``attn.window`` are the window layers' reads and those that hold
neither that nor ``moe.experts`` the full layers'; the two add up to
``_pool_attn.decode_ms``. Every function returns ``None`` where there is
nothing to read: no trace, a decode program that is not told apart, a program
with no such call (the XLA read; a program from before the scope)."""

from benchmarks.layer_metrics import _program_spans, _serve

MOSAIC = 'custom_call_target="tpu_custom_call"'
WINDOW, EXPERTS = "attn.window", "moe.experts"
# the grouped matmuls XLA makes of the sorted experts' ``lax.ragged_dot``:
# Mosaic calls of a prefill program that lose the scope path and are named
# ``ragged-dot-*`` (the workload file's ``moe_scopes`` finds them so too)
RAGGED = "ragged-dot"


def kind_of(name: str):
    """``"window"``, ``"full"``, ``"experts"`` for a Mosaic call's name, or
    ``None`` for another op."""
    if MOSAIC not in name:
        return None
    if EXPERTS in name or RAGGED in name:
        return "experts"
    return "window" if WINDOW in name else "full"


def mosaic_ns(trace, keys, kinds):
    """``(ns, executions)``: the device time of the Mosaic calls of
    ``kinds`` (``kind_of``) that start inside an execution of the programs
    ``keys`` in the traced window, and how many executions there were."""
    lo, hi = trace.window()
    ns, runs_seen = 0.0, 0
    for d in trace.devices.values():
        runs = [(e.start, e.end) for e in d.modules
                if lo <= e.start < hi and e.name.strip() in keys]
        runs_seen += len(runs)
        ns += sum(e.dur for e in d.ops if kind_of(e.name) in kinds
                  and any(a <= e.start < b for a, b in runs))
    return ns, runs_seen


def decode_ms(trace, counters, ctx, kind: str):
    """ms a decode step spends in the pool-read kernel of the layers of
    ``kind`` (``"window"`` | ``"full"``), all of them together."""
    key = _serve._decode_id(trace, counters, ctx)
    if key is None:
        return None
    ns, steps = mosaic_ns(trace, {key}, (kind,))
    return ns / 1e6 / steps if ns and steps else None


def rows_per_step(trace, kind: str):
    """K/V rows the dispatched slots held in the layers of ``kind``, a decode
    step: the mean of the ``kv_rows_window`` / ``kv_rows_full`` attr over the
    program's ``serve.decode`` spans **in the trace** that carry it (those
    that dispatched). The traced seconds and no others: a few prompts of
    tens of thousands of tokens hold most of a step's rows, so the whole
    window's mean is not the mean of the steps whose kernels were timed (a
    first reading against the window's mean came out at 110 %; PERF.md
    section 6, PR 44)."""
    attr = f"kv_rows_{kind}"
    rows = [float(e.stats[attr])
            for e in _program_spans.spans(trace, "serve.decode") or ()
            if attr in e.stats]
    return sum(rows) / len(rows) if rows else None


def roofline_pct(trace, counters, ctx, kind: str):
    """The least time the chip needs to read the rows the live slots hold in
    the layers of ``kind`` (``rows_per_step`` times ``mixed_attn_cost.
    kv_row_bytes``) over the kernel's time there, in per cent."""
    from benchmarks.lib import mixed_attn_cost, peaks

    ms = decode_ms(trace, counters, ctx, kind)
    rows = rows_per_step(trace, kind) if ms else None
    if not rows or not ms:
        return None
    least = rows * mixed_attn_cost.kv_row_bytes(ctx["config"]) / (
        peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
