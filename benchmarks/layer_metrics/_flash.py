"""Shared by the flash metrics: find the Mosaic kernels' device events and the
number of training steps in the traced window."""

import re

from benchmarks.lib import xplane


def kernel_events(trace, ctx):
    """``{kernel: [Event]}`` for the cell's ``flash_kernels`` patterns
    (regular expressions over the op's name and kept stats)."""
    pats = ctx["cell"].get("flash_kernels")
    if not pats or not trace.devices:
        return None
    return {k: xplane.ops_matching(trace, p) for k, p in pats.items()}


def steps_in_trace(trace, ctx):
    pat = ctx["cell"].get("step_program")
    if not pat:
        return 0
    rx = re.compile(pat)
    return sum(len(v) for k, v in xplane.module_times(trace).items()
               if rx.search(k))
