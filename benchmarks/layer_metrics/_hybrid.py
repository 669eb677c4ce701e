"""Shared by the hybrid model's metrics: device time of the ops under one of
the program's scopes (``kda.*``, ``mla.*``, the routed share's ``moe.*``)
inside the decode program's executions or the prefill programs', by
``_moe.scoped_seconds`` with the cell's own pattern for that part
(``<part>_scopes`` in the workload file). ``None`` where there is nothing to
read: no trace, a program without the scopes (the parent of PR 29), a cell
without the key."""

from benchmarks.layer_metrics import _moe
from benchmarks.lib import peaks


def scoped(trace, counters, ctx, part, scopes=None):
    """``_moe.scoped_seconds`` for the scopes the cell lists under
    ``<part>_scopes``."""
    pattern = ctx["cell"].get(part + "_scopes")
    if not pattern:
        return None
    cell = {**ctx["cell"], "moe_scopes": pattern}
    return _moe.scoped_seconds(trace, counters, {**ctx, "cell": cell}, scopes)


def decode_ms(trace, counters, ctx, part, scopes=None):
    """ms a decode step spends under the part's scopes, all layers."""
    found = scoped(trace, counters, ctx, part, scopes)
    if not found or not found["decode"][1]:
        return None
    seconds, steps = found["decode"]
    return 1e3 * seconds / steps


def roofline_pct(trace, counters, ctx, part, nbytes, scopes=None):
    """The least time the chip needs to move ``nbytes`` a step over the
    device time of the part's scopes in a decode step, in per cent."""
    ms = decode_ms(trace, counters, ctx, part, scopes)
    if not ms:
        return None
    least = nbytes / peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)
