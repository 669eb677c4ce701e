"""``lib/program_spans.py`` and the eight span metrics against a made-up trace
whose numbers are worked by hand here, and against a real profile of the
program's tracer taken on whatever JAX finds. Run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import importlib

import pytest

from benchmarks.lib import program_spans as ps
from benchmarks.lib import xplane
from benchmarks.lib.xplane import DeviceTrace, Event, Trace
from benchmarks.tools import span_report


def _reader(name):
    return importlib.import_module("benchmarks.layer_metrics." + name)


def _ev(name, start, end, **stats):
    return Event(name, float(start), float(end - start),
                 {k: str(v) for k, v in stats.items()})


def serve_trace():
    """10,000 ns of a server: step 1 admits two requests (a prefill program
    and one eager scatter each) and decodes; step 2 only decodes.

    device 0 busy:  500-900  950-1000  1100-1500  1600-1700  2500-5500
                    6500-9500                                  = 6,950 ns
    so idle:        0-500  900-950  1000-1100  1500-1600  1700-2500
                    5500-6500  9500-10000                      = 3,050 ns
    """
    programs = [("jit__unknown(3)", 500, 900), ("jit_scatter(9)", 950, 1000),
                ("jit__unknown(3)", 1100, 1500), ("jit_scatter(9)", 1600, 1700),
                ("jit__unknown(5)", 2500, 5500), ("jit__unknown(5)", 6500, 9500)]
    dev0 = DeviceTrace(ops=[_ev("%fusion.1", a, b) for _, a, b in programs],
                       modules=[_ev(n, a, b) for n, a, b in programs])
    # a second device that idles all the time: the span metrics read the first
    dev1 = DeviceTrace(ops=[_ev("%fusion.1", 0, 10)], modules=[])
    host = [
        _ev("bench.trace_window", 0, 10_000),
        _ev("dl4j.serve.step", 100, 5900),
        _ev("dl4j.serve.admit", 200, 2000),
        _ev("dl4j.serve.prefill", 300, 1000, request=1, queue_wait_us=1500),
        _ev("dl4j.serve.prefill", 1050, 1800, request=2, queue_wait_us=2500),
        _ev("dl4j.serve.decode", 2100, 5600, live=2, kind="plain"),
        _ev("dl4j.serve.emit", 5600, 5850),
        _ev("dl4j.serve.step", 6000, 9900),
        _ev("dl4j.serve.decode", 6100, 9600, live=2, kind="plain"),
        _ev("dl4j.serve.emit", 9600, 9850),
    ]
    # the runtime's launch of each program, on the host's clock, 20-150 ns
    # before the device starts it
    host += [_ev(ps.LAUNCH, a, a + 10)
             for a in (450, 930, 1080, 1580, 2350, 6400)]
    host.sort(key=lambda e: (e.start, -e.dur))
    return Trace({0: dev0, 1: dev1}, host)


def train_trace():
    """Two ``fit_batch`` calls of 300 ns; the device idles 50 + 20 ns inside
    the first, 20 ns inside the second, and 220 ns between them."""
    dev = DeviceTrace(ops=[_ev("%fusion.2", 150, 380), _ev("%fusion.2", 620, 900)],
                      modules=[_ev("jit_step(7)", 150, 380),
                               _ev("jit_step(7)", 620, 900)])
    host = [_ev("bench.trace_window", 0, 1000),
            _ev("dl4j.train.step", 100, 400, step=4),
            _ev("dl4j.train.sync", 200, 395),
            _ev("dl4j.train.step", 600, 900, step=5),
            _ev("dl4j.train.sync", 700, 898)]
    return Trace({0: dev}, host)


# --------------------------------------------------------------------------
# the interval helpers
# --------------------------------------------------------------------------
def test_device_idle_is_the_window_less_the_ops_of_the_first_device():
    idle = ps.device_idle(serve_trace())
    assert idle == [(0, 500), (900, 950), (1000, 1100), (1500, 1600),
                    (1700, 2500), (5500, 6500), (9500, 10_000)]
    assert xplane.total(idle) == 3050
    assert ps.device_idle(serve_trace(), device=1) == [(10, 10_000)]
    assert ps.device_idle(Trace()) == []


def test_overlap_and_launches_inside():
    trace = serve_trace()
    admit = ps.intervals(ps.named(ps.load(trace), "serve.admit"))
    assert admit == [(200, 2000)]
    # 200-500, 900-950, 1000-1100, 1500-1600, 1700-2000
    assert ps.overlap_ns(ps.device_idle(trace), admit) == 850
    launched = ps.launches(trace)
    assert [e.start for e in launched] == [450, 930, 1080, 1580, 2350, 6400]
    assert ps.starts_inside(launched, admit) == 4
    decode = ps.intervals(ps.named(ps.load(trace), "serve.decode"))
    assert ps.starts_inside(launched, decode) == 2
    assert ps.starts_inside(launched, []) == 0
    assert ps.starts_inside([], admit) == 0


def test_a_device_timeline_that_lies_early_is_moved_back_by_causality():
    """The chip read the device's timeline 0.9 ms before the host's in one
    trace (PR 23): a program then seems to start before its launch. Launches
    are counted on the host's clock; the idle intervals are of the device's
    timeline moved by the median of launch less start."""
    trace = serve_trace()
    # launch less start in order: -50 -20 -20 -20 -150 -100: never positive
    assert ps.device_lead_ns(trace) == 0.0
    for e in trace.devices[0].ops + trace.devices[0].modules:
        e.start -= 320          # the first prefill program now 'starts' at 180
    # now 270 300 300 300 170 220: median (270 + 300) / 2
    assert ps.device_lead_ns(trace) == 285.0
    assert _reader("admit_programs_per_request").compute(
        trace, None, {}, {}) == 2.0
    # moved back by 285 of the 320: each busy interval lies 35 ns early,
    # the first prefill program at 465-865, the last decode at 6465-9465
    assert ps.device_idle(trace)[:2] == [(0, 465), (865, 915)]
    assert ps.device_idle(trace)[-1] == (9465, 10_000)
    trace.host = [e for e in trace.host if e.name != ps.LAUNCH][:-1] + [
        _ev(ps.LAUNCH, 450, 460)]
    assert ps.device_lead_ns(trace) == 0.0      # one launch, six executions


# --------------------------------------------------------------------------
# the eight readers, by hand
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name,trace,want", [
    # idle inside serve.admit: 850 of 10,000 ns
    ("admit_idle_pct", serve_trace, 8.5),
    ("sat_admit_idle_pct", serve_trace, 8.5),
    # steps less admission: 100-200, 2000-5900, 6000-9900; idle there:
    # 100 + (2000-2500) + (5500-5900) + (6000-6500) + (9500-9900) = 1,900
    ("step_host_idle_pct", serve_trace, 19.0),
    # one admission of 1,800 ns over two decode spans
    ("admit_ms_per_step", serve_trace, 0.0009),
    # launches in 200-2000 (450, 930, 1080, 1580) over 2 prefills
    ("admit_programs_per_request", serve_trace, 2.0),
    # median of 1,500 and 2,500 us
    ("queue_wait_p50_ms", serve_trace, 2.0),
    # step starts 100 and 6000
    ("sat_step_cycle_ms", serve_trace, 0.0059),
    # (50 + 20 + 20) ns over two train.step spans
    ("lm_step_host_idle_ms", train_trace, 0.000045),
])
def test_reader_against_the_made_up_trace(name, trace, want):
    got = _reader(name).compute(trace(), None, {}, {"cell": {}})
    assert got == pytest.approx(want, rel=1e-12)


def test_the_idle_shares_add_up_to_the_harness_idle_share():
    """admit + (step less admit) + outside every step = 100 x (1 - busy /
    window) of the first device: 8.5 + 19.0 + 3.0 = 30.5."""
    trace = serve_trace()
    trace.devices.pop(1)            # busy_and_window averages over devices
    busy_s, window_s = xplane.busy_and_window(trace)
    assert 100 * (1 - busy_s / window_s) == pytest.approx(30.5)
    rows, outside_ms = span_report.report(trace, ps.load(trace),
                                          ps.launches(trace))
    assert outside_ms == pytest.approx(300 / 1e6)        # 0-100, 5900-6000, 9900-
    by_name = {r[0]: r for r in rows}
    # serve.admit's own idle leaves out what lies inside its prefills:
    # 200-300 (100) and 1800-2000 (200) and 1000-1050 (50)
    assert by_name["dl4j.serve.admit"][3] == pytest.approx(350 / 1e6)
    assert by_name["dl4j.serve.prefill"][1:] == pytest.approx(
        (2, 1450 / 1e6, 500 / 1e6, 4))
    assert sum(r[3] for r in rows) + outside_ms == pytest.approx(3050 / 1e6)


@pytest.mark.parametrize("name", [
    "admit_idle_pct", "sat_admit_idle_pct", "step_host_idle_pct",
    "admit_ms_per_step", "admit_programs_per_request", "queue_wait_p50_ms",
    "sat_step_cycle_ms", "lm_step_host_idle_ms"])
def test_reader_finds_nothing_in_a_program_without_the_spans(name, tmp_path,
                                                             monkeypatch):
    """The parent of PR 23 opens no ``dl4j.*`` span: nothing, and no raise."""
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    trace = serve_trace()
    trace.host = [e for e in trace.host if e.name.startswith("bench.")]
    assert _reader(name).compute(trace, None, {}, {"cell": {}}) is None
    assert _reader(name).compute(Trace(), None, {}, {"cell": {}}) is None


# --------------------------------------------------------------------------
# a real profile of the program's tracer
# --------------------------------------------------------------------------
@pytest.fixture()
def profiled(tmp_path, monkeypatch):
    """A checkout whose ``.bench_out`` holds one trace taken the way the
    harness takes it, round spans the program's tracer opened."""
    import jax

    from deeplearning4j_tpu.monitor import SpanTracer

    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    log_dir = tmp_path / ".bench_out" / "trace-made-up"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t = SpanTracer()
    f = jax.jit(lambda x: x + 1)
    f(1.0).block_until_ready()
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        with t.span("serve.step"):
            with t.span("serve.admit"):
                with t.span("serve.prefill", request=41, slot=0,
                            prompt_len=19, bucket=32, queue_wait_us=1234):
                    f(1.0).block_until_ready()
            t.record("serve.queued", 0.0, 1.0, request=41)  # the ring only
    jax.profiler.stop_trace()
    return (tmp_path / ".bench_out",
            xplane.load_xplane(xplane.find_xplane(str(log_dir))))


def test_load_reopens_the_xplane_whose_window_matches(profiled):
    out_dir, trace = profiled
    assert [e.name for e in trace.host] == ["bench.trace_window"]
    events = ps.load(trace)
    assert [e.name for e in events] == ["dl4j.serve.step", "dl4j.serve.admit",
                                        "dl4j.serve.prefill"]
    lo, hi = trace.window()
    assert all(lo <= e.start and e.end <= hi for e in events)
    step, admit, prefill = events
    assert step.start <= admit.start <= prefill.start
    assert prefill.end <= admit.end <= step.end
    assert prefill.stats == {"request": "41", "slot": "0", "prompt_len": "19",
                             "bucket": "32", "queue_wait_us": "1234"}
    assert _reader("queue_wait_p50_ms").compute(
        trace, None, {}, {"cell": {}}) == pytest.approx(1.234)
    # no device plane on this backend: the joins to the device give nothing
    assert _reader("admit_idle_pct").compute(trace, None, {}, {}) is None


def test_no_matching_xplane_gives_nothing_and_says_so(profiled, capsys):
    out_dir, trace = profiled
    lo, hi = trace.window()
    other = Trace(host=[Event("bench.trace_window", lo + 1, hi - lo)])
    assert ps.load(other, out_dir=str(out_dir)) == []
    assert "no xplane under" in capsys.readouterr().err
    assert ps.load(trace, out_dir=str(out_dir / "nowhere")) == []
    assert ps.load(Trace(), out_dir=str(out_dir)) == []     # no window at all
