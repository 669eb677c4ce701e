"""``lib/xplane.py``: interval arithmetic on made-up events, then the numbers of
a small trace recorded on the chip (``recorded_sc2_train_8k.json.gz``, a slice
of ``sc2-train-8k`` from PR 22, trimmed by ``tools/trace_report.py --record``)."""

import gzip
import json
import os

import pytest

from benchmarks.layer_metrics import _serve
from benchmarks.lib import xplane
from benchmarks.lib.xplane import DeviceTrace, Event, Trace
from benchmarks.tools import trace_report

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_sc2_train_8k.json.gz")


def ev(name, start, dur, **stats):
    return Event(name, float(start), float(dur), stats)


def test_union_clip_subtract():
    u = xplane.union([(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)])
    assert u == [(0, 15), (20, 31)]
    assert xplane.total(u) == 26
    assert xplane.clip(u, 10, 25) == [(10, 15), (20, 25)]
    assert xplane.subtract([(0, 40)], u) == [(15, 20), (31, 40)]
    assert xplane.subtract([(0, 5), (10, 20)], [(3, 12), (18, 25)]) == [
        (0, 3), (12, 18)]


def _toy():
    ops = [ev("while.1", 100, 400),             # a container ...
           ev("%fusion.2 = f32[8,8]{1,0:T(8,128)} fusion(f32[8,8]{1,0} %p.1), "
              "kind=kLoop", 100, 150),                     # ... its body
           ev("all-reduce.3", 250, 100),
           ev("%fusion.4 = f32[8,8]{1,0:T(8,128)} fusion(f32[8,8]{1,0} %p.2), "
              "kind=kLoop", 350, 150),
           ev("fusion.9", 700, 100),
           ev("%all-reduce-start.5 = f32[4]{0} all-reduce-start(f32[4]{0} %g)",
              800, 10),
           ev("fusion.6", 810, 80),
           ev("all-reduce-done.5", 890, 30)]
    mods = [ev("jit_step(7)", 100, 400), ev("jit_other(8)", 700, 220)]
    host = [ev("bench.trace_window", 0, 1000),
            ev("bench.lm_step", 50, 500), ev("bench.lm_step", 560, 400)]
    return Trace({0: DeviceTrace(ops, mods)}, host)


def test_busy_idle_and_gaps_on_a_toy_trace():
    t = _toy()
    assert t.window() == (0.0, 1000.0)
    busy, window = xplane.busy_and_window(t)
    assert window == pytest.approx(1000e-9)
    assert busy == pytest.approx((400 + 220) * 1e-9)
    gaps = xplane.idle_gaps(t, min_ns=1)
    assert [(a, b) for a, b, _ in gaps] == [(0, 100), (500, 700), (920, 1000)]
    assert [g[2] for g in gaps] == ["lm_step after start",
                                    "lm_step after jit_step",
                                    "lm_step after jit_other"]
    assert xplane.top_idle_gaps(t, 1, min_ns=1) == [["lm_step after jit_step", 200e-9]]


def test_self_time_programs_and_collectives_on_a_toy_trace():
    t = _toy()
    selfs = {e.name: s for e, s in xplane.self_times(t.devices[0].ops)}
    assert selfs["while.1"] == 0.0              # its children fill it
    assert selfs["fusion.9"] == 100
    top = dict(xplane.top_ops(t))
    # a TPU names an op by its whole HLO instruction: label = name without
    # its number + first result shape
    assert top["fusion f32[8,8]"] == pytest.approx(300e-9)
    assert top["all-reduce"] == pytest.approx(100e-9)
    assert top["all-reduce-start f32[4]"] == pytest.approx(10e-9)
    assert xplane.module_times(t) == {"jit_step": [400e-9],
                                      "jit_other": [220e-9]}
    # collectives: 100 + 10 + 30 ns; nothing else runs during any of them
    assert xplane.collective_seconds(t) == {
        0: (pytest.approx(140e-9), pytest.approx(140e-9))}
    assert len(xplane.ops_matching(t, r"^%fusion[.0-9]* = f32\[8,8\]")) == 2


def test_recorded_round_trip():
    t = _toy()
    again = Trace.from_recorded(json.loads(json.dumps(
        trace_report.recorded_form(t))))
    assert xplane.busy_and_window(again) == xplane.busy_and_window(t)
    assert xplane.top_ops(again) == xplane.top_ops(t)


def test_decode_program_is_told_apart_by_the_step_counter_or_not_at_all(capsys):
    """Every serving program is ``jit__unknown``: decode is the one whose runs
    in the trace number the server's steps under the profiler."""
    mods, t = [], 0
    for i in range(40):                       # 40 decode steps, 7 + 5 prefills
        mods.append(ev("jit__unknown(7)", t, 30)); t += 40
        if i % 6 == 0:
            mods.append(ev("jit__unknown(9)", t, 100)); t += 110
        if i % 8 == 0:
            mods.append(ev("jit__unknown(11)", t, 200)); t += 210
    mods.append(ev("jit_add(3)", t, 5))
    trace = Trace({0: DeviceTrace([], mods)}, [])
    cell = {"cell": {
        "decode_program": {"module": "^jit__unknown$",
                           "runs": "decode_steps_in_trace"},
        "prefill_program": {"module": "^jit__unknown$",
                            "except": "decode_program"}}}
    counted = {"decode_steps_in_trace": 41}   # the profiler's edge lost one
    assert _serve.median_decode_ms(trace, counted, cell) == pytest.approx(30e-6)
    assert sorted(_serve.prefill_seconds(trace, counted, cell)) == (
        [pytest.approx(100e-9)] * 7 + [pytest.approx(200e-9)] * 5)
    # no program ran that often, or the driver counted nothing: no guess
    assert _serve.median_decode_ms(trace, {"decode_steps_in_trace": 8},
                                   cell) == pytest.approx(100e-6)
    assert _serve.median_decode_ms(trace, {"decode_steps_in_trace": 20},
                                   cell) is None
    assert "not told apart" in capsys.readouterr().err
    assert _serve.prefill_seconds(trace, {"decode_steps_in_trace": 20},
                                  cell) == []
    assert _serve.median_decode_ms(trace, {}, cell) is None
    # two candidates: 7 and 5 runs are both within the edge of 6
    assert _serve.median_decode_ms(trace, {"decode_steps_in_trace": 6},
                                   cell) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return Trace.from_recorded(json.load(f))


def test_recorded_chip_trace_busy_idle_and_programs(recorded):
    """700 ms of ``sc2-train-8k`` on a TPU v5 lite (my chip run, PR 22):
    the end of one step, one whole step, the start of a third."""
    lo, hi = recorded.window()
    assert hi - lo == pytest.approx(700e6)
    busy, window = xplane.busy_and_window(recorded)
    assert window == pytest.approx(0.7)
    assert busy == pytest.approx(0.692950276, abs=1e-9)
    steps = xplane.module_times(recorded)["jit_step"]
    assert steps == pytest.approx([0.180607031, 0.27957375, 0.232788515])
    # idle: two gaps between steps, each split by the tiny batch program
    assert len(xplane.idle_gaps(recorded)) == 4
    assert xplane.top_idle_gaps(recorded) == [
        ["lm_step after jit_step", pytest.approx(0.003970046, abs=1e-9)],
        ["lm_step after jit_make_batch", pytest.approx(0.003072061, abs=1e-9)]]
    assert busy + 0.003970046 + 0.003072061 == pytest.approx(0.7, abs=1e-5)


def test_recorded_chip_trace_kernel_sums(recorded):
    with open(os.path.join(HERE, "..", "workloads", "sc2-train-8k.json")) as f:
        pats = json.load(f)["flash_kernels"]
    got = {k: xplane.ops_matching(recorded, p) for k, p in pats.items()}
    assert {k: len(v) for k, v in got.items()} == {"fwd": 8, "dkdv": 11,
                                                   "dq": 11}
    assert {k: sum(e.dur for e in v) for k, v in got.items()} == {
        "fwd": 37435507.0, "dkdv": 60266274.0, "dq": 53427109.0}
    top = xplane.top_ops(recorded, 3)
    assert top[0] == ["transpose_jvp___ f32[24,8192,128] [mosaic]",
                      pytest.approx(0.113693383, abs=1e-9)]
    assert top[1][0] == "fusion bf16[3072]"
    assert xplane.collective_seconds(recorded) == {0: (0.0, 0.0)}
