"""``decode_attn_ms_per_step`` on a small made-up trace: the Mosaic calls of
the decode program and of no other. Run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_decode_attn_metric.py -q
"""

import json
import os

import pytest

from benchmarks.layer_metrics import decode_attn_ms_per_step as reader
from benchmarks.lib import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KERNEL = ('%custom-call.{} = bf16[64,32,128]{{2,1,0}} custom-call(%a), '
          'custom_call_target="tpu_custom_call"')
CTX = {"cell": {"decode_program": {"module": "^jit__unknown$",
                                   "runs": "decode_steps_in_trace"},
                "prefill_program": {"module": "^jit__unknown$",
                                    "except": "decode_program"}}}


def _trace(kernel_ns=(30_000.0, 20_000.0)):
    """Program (7) runs ten times in the window, each time with a fusion and
    the kernel calls of ``kernel_ns``, and once before it; program (9), a
    prefill, runs once with a flash kernel of its own."""
    def ev(name, start, dur):
        return xplane.Event(name, float(start), float(dur))

    mods, ops = [ev("jit__unknown(7)", 100_000, 400_000)], [
        ev(KERNEL.format(1), 110_000, 77_000)]
    for i in range(10):
        t = 1_000_000 + 1_000_000 * i
        mods.append(ev("jit__unknown(7)", t, 500_000))
        ops += [ev("%fusion.1 = bf16[64,1]", t, 100_000)]
        ops += [ev(KERNEL.format(n), t + 100_000 * (n + 1), dur)
                for n, dur in enumerate(kernel_ns)]
    mods.append(ev("jit__unknown(9)", 11_500_000, 300_000))
    ops.append(ev(KERNEL.format(5), 11_600_000, 90_000))
    host = [ev("bench.trace_window", 900_000, 11_200_000)]
    return xplane.Trace({0: xplane.DeviceTrace(ops, mods)}, host)


def test_kernel_time_of_the_decode_program_per_step():
    counters = {"decode_steps_in_trace": 10}
    assert reader.compute(_trace(), None, counters, CTX) == pytest.approx(
        0.05)
    # nothing to read, nothing raised: a decode program that is not told
    # apart, one with no Mosaic call (the XLA read), a cell without the key,
    # a run with no trace
    assert reader.compute(_trace(), None, {"decode_steps_in_trace": 5},
                          CTX) is None
    assert reader.compute(_trace(()), None, counters, CTX) is None
    assert reader.compute(_trace(), None, counters, {"cell": {}}) is None
    assert reader.compute(xplane.Trace(), None, counters, CTX) is None


def test_benchmark_json_lists_it_for_the_cells_that_report_tpot():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": reader.NAME, "unit": reader.UNIT, "better": "lower",
        "source": "device_trace", "layer": reader.LAYER,
        "moves": reader.MOVES,
        "workloads": ["sc2-serve-steady", "olmoe-serve-chat"]}
    tpot = next(m for m in bench["end_to_end"] if m["name"] == reader.MOVES)
    assert set(entry["workloads"]) <= set(tpot["workloads"])
