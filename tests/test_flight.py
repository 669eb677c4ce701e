# dl4j-lint: skip-file -- rule-fixture corpus: snippet strings in this file are seeded violations and would (correctly) trip the rules they test
"""Run-level observability tests (PR 9): the RunLedger goodput/badput
classification, the crash-surviving flight recorder, the postmortem
end-state classifier, the fleet heartbeat telemetry, and the
chunk-boundary-only lint contract.

The contracts that matter most:

1. The ledger + flight recorder are OBSERVATIONAL: trained params with
   the recorder live are bitwise-identical to off (FF/RNN/graph + the
   SPMD wrapper).
2. Crash forensics: a fused-run subprocess killed -9 mid-chunk leaves
   segments from which ``flight_report`` reconstructs the timeline and
   classifies the death as ``crashed``; a blocked acquisition with
   heartbeats marching on classifies as ``wedged``.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from deeplearning4j_tpu.analysis.engine import LintConfig, run_lint
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.monitor import (
    SpanTracer,
    metrics,
    set_tracer,
    telemetry_summary,
    tracer,
)
from deeplearning4j_tpu.monitor.exporters import JsonlExporter
from deeplearning4j_tpu.monitor.flight import (
    FlightRecorder,
    classify_end_state,
    flight_record,
    load_flight_records,
    set_flight,
    shift_rotate,
)
from deeplearning4j_tpu.monitor.ledger import (
    RunLedger,
    run_ledger,
    set_run_ledger,
)
from deeplearning4j_tpu.monitor.trace import Span
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.losses import LossFunction
from deeplearning4j_tpu.parallel.cluster import HeartbeatMonitor
from deeplearning4j_tpu.parallel.statetracker import (
    FileStateTracker,
    InMemoryStateTracker,
    StateTracker,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLIGHT_REPORT = os.path.join(REPO, "scripts", "flight_report.py")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


flight_report = _load_script("flight_report")
bench_report = _load_script("bench_report")


@pytest.fixture(autouse=True)
def _fresh_global_telemetry():
    """Fresh registry/tracer/ledger and NO flight recorder per test."""
    metrics().reset()
    set_tracer(SpanTracer())
    set_run_ledger(RunLedger())
    set_flight(None)
    yield
    metrics().reset()
    set_tracer(None)
    set_run_ledger(None)
    set_flight(None)


# ---------------------------------------------------------------------------
# model/data helpers (the test_telemetry shapes)
# ---------------------------------------------------------------------------


def _ff_net(seed=0):
    conf = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
        .updater(Updater.ADAM).list()
        .layer(0, L.DenseLayer(n_in=6, n_out=12, activation="tanh"))
        .layer(1, L.OutputLayer(n_in=12, n_out=3))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _rnn_net(seed=0):
    conf = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.02)
        .updater(Updater.SGD).list()
        .layer(0, L.GravesLSTM(n_in=3, n_out=6, activation="tanh"))
        .layer(1, L.RnnOutputLayer(n_in=6, n_out=4,
                                   loss_function=LossFunction.MCXENT))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _ff_graph(seed=0):
    g = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
        .updater(Updater.ADAM)
        .graph_builder()
        .add_inputs("in")
        .add_layer("dense", L.DenseLayer(n_in=6, n_out=12,
                                         activation="tanh"), "in")
        .add_layer("out", L.OutputLayer(n_in=12, n_out=3), "dense")
        .set_outputs("out")
    )
    return ComputationGraph(g.build())


def _ff_data(n=48, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


def _rnn_data(n=24, t=5, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, t, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (n, t))]
    return DataSet(x, y)


def _leaves_equal(a, b):
    import jax

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        (np.asarray(x) == np.asarray(y)).all() for x, y in zip(la, lb))


def _span(name, start, end, **attrs):
    sp = Span(name, 0, None, start, attrs)
    sp.end_s = end
    return sp


def _event(name, at, **attrs):
    return _span(name, at, at, **attrs)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# RunLedger: the wall-time classification
# ---------------------------------------------------------------------------


class TestRunLedger:
    def test_classification_priorities_and_goodput(self):
        """The worked example: a 25 s window with one run, blocking and
        background badput, and every priority rule exercised."""
        clock = FakeClock(0.0)
        spans = [
            _span("checkpoint.write", 2, 3),             # foreground
            _span("cache.build", 5, 10),
            _span("retry.sleep", 12, 13),                 # inside run
            _span("checkpoint.write", 14, 18, background=True),  # hidden
            _event("watchdog.stall", 16, stalled_s=2.0),  # covers 14-16
        ]
        ledger = RunLedger(clock=clock, span_source=lambda: spans)
        clock.t = 10.0
        ledger.run_start(model="X", epochs=2)
        clock.t = 20.0
        ledger.run_end(status="clean")
        clock.t = 25.0
        rep = ledger.report()
        st = rep["states"]
        assert st["checkpoint"] == pytest.approx(1.0)
        assert st["cache_build"] == pytest.approx(5.0)
        assert st["retry_backoff"] == pytest.approx(1.0)
        assert st["watchdog_stall"] == pytest.approx(2.0)
        # compute = run window minus the retry second and the stall pair
        assert st["compute"] == pytest.approx(7.0)
        assert st["idle"] == pytest.approx(9.0)
        # goodput excludes idle: 7 / (25 - 9)
        assert rep["goodput_pct"] == pytest.approx(100 * 7 / 16, abs=0.01)
        # the background write never became badput, but is visible
        assert rep["hidden_checkpoint_s"] == pytest.approx(4.0)
        assert rep["badput"] == {"checkpoint": 1.0, "cache_build": 5.0,
                                 "retry_backoff": 1.0,
                                 "watchdog_stall": 2.0}

    def test_per_run_report_cached_at_run_end(self):
        clock = FakeClock(0.0)
        spans = [_span("retry.sleep", 12, 13)]
        ledger = RunLedger(clock=clock, span_source=lambda: spans)
        clock.t = 10.0
        ledger.run_start(model="MLN", epochs=3)
        for _ in range(3):
            ledger.chunk_start()
            clock.t += 2.0
            ledger.chunk_done()
        rep = ledger.run_end(status="clean")
        # within [10, 16]: 1 s retry, 5 s compute
        assert rep["goodput_pct"] == pytest.approx(100 * 5 / 6, abs=0.01)
        assert ledger.last_run_goodput() == rep["goodput_pct"]
        run = ledger.report()["runs"][0]
        assert run["chunks"] == 3
        assert run["status"] == "clean"
        assert run["wall_s"] == pytest.approx(6.0)
        assert run["host_dispatch_s"] == pytest.approx(6.0)
        assert run["model"] == "MLN"

    def test_grant_wait_outranks_everything(self):
        clock = FakeClock(0.0)
        spans = [
            _span("grant.acquire", 0, 8),
            _span("cache.build", 4, 6),  # overlapped: grant wins
        ]
        ledger = RunLedger(clock=clock, span_source=lambda: spans)
        clock.t = 8.0
        st = ledger.report()["states"]
        assert st["grant_wait"] == pytest.approx(8.0)
        assert st["cache_build"] == 0.0

    def test_active_run_counts_up_to_now(self):
        clock = FakeClock(0.0)
        ledger = RunLedger(clock=clock, span_source=lambda: [])
        ledger.run_start(model="X", epochs=1)
        clock.t = 4.0
        rep = ledger.report()
        assert rep["run_in_flight"] is True
        assert rep["states"]["compute"] == pytest.approx(4.0)
        assert rep["goodput_pct"] == pytest.approx(100.0)

    def test_drive_epoch_chunks_populates_ledger(self):
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 3,
                       chunk_epochs=1)
        rep = run_ledger().report()
        assert rep["n_runs"] == 1
        run = rep["runs"][0]
        assert run["status"] == "clean"
        assert run["chunks"] == 3
        assert run["model"] == "MultiLayerNetwork"
        assert run["goodput_pct"] is not None and run["goodput_pct"] > 0

    def test_telemetry_summary_embeds_ledger_block(self):
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 2,
                       chunk_epochs=1)
        block = telemetry_summary()["ledger"]
        assert block["n_runs"] == 1
        assert set(block["states"]) >= {"compute", "idle", "grant_wait"}
        json.dumps(block)  # artifact-embeddable

    def test_diverged_run_closes_with_error_status(self):
        from deeplearning4j_tpu.resilience.guard import (
            TrainingDivergedError)

        net = _ff_net()
        data = _ff_data()
        data.features = np.asarray(data.features)
        data.features[3, :] = np.nan
        with pytest.raises(TrainingDivergedError):
            net.fit_epochs(ListDataSetIterator(data, 12), 2,
                           chunk_epochs=1, guard="raise")
        runs = run_ledger().report()["runs"]
        assert runs and runs[-1]["status"].startswith("error:")
        # the run's own span closed too, with what failed stamped on it
        (run,) = [s for s in tracer().spans() if s.name == "epoch.run"]
        assert run.end_s is not None
        assert run.attrs["error"].startswith("TrainingDivergedError")

    def test_a_fit_epochs_call_is_one_epoch_run_span(self):
        """``epoch.run`` holds every chunk's dispatch and readback; its
        attrs are the ledger's ``run_start`` ones, all scalars (they reach
        the profiler)."""
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 3,
                       chunk_epochs=1, guard="halve_lr")
        spans = tracer().spans()
        (run,) = [s for s in spans if s.name == "epoch.run"]
        assert run.attrs == {"model": "MultiLayerNetwork", "epochs": 3,
                             "steps": 12, "chunk_epochs": 1,
                             "guard": "halve_lr"}
        assert "error" not in run.attrs and run.parent_id is None
        inside = [s for s in spans
                  if s.name in ("epoch.chunk", "epoch.readback")]
        # a dispatch and a sentinel read a chunk, the flush at the end
        assert len(inside) == 7
        assert all(s.parent_id == run.span_id for s in inside)
        assert all(run.start_s <= s.start_s and s.end_s <= run.end_s
                   for s in inside)

    def test_the_ledger_books_nothing_for_epoch_run(self):
        """A span name the ledger does not know moves no state: the report
        of a window with an ``epoch.run`` span is the report without."""
        def report(spans):
            clock = FakeClock(0.0)
            ledger = RunLedger(clock=clock, span_source=lambda: spans)
            clock.t = 2.0
            ledger.run_start(model="X", epochs=2)
            clock.t = 9.0
            ledger.run_end(status="clean")
            clock.t = 10.0
            return ledger.report()

        spans = [_span("cache.build", 0, 2), _span("epoch.chunk", 2, 3),
                 _span("retry.sleep", 4, 5)]
        assert report(spans + [_span("epoch.run", 2, 9, model="X")]) \
            == report(spans)


# ---------------------------------------------------------------------------
# FlightRecorder: the on-disk ring
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_records_round_trip_and_heartbeats(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), heartbeat_s_=0.05)
        rec.record("run.start", model="X", epochs=3)
        rec.record("chunk.done", epoch0=0)
        assert rec.flush()
        time.sleep(0.12)  # at least one heartbeat lands
        rec.close()
        records = load_flight_records(str(tmp_path))
        kinds = [r["kind"] for r in records]
        assert kinds[0] == "run.start"
        assert "chunk.done" in kinds
        assert "flight.heartbeat" in kinds
        assert kinds[-1] == "flight.close"
        assert all("t_wall" in r for r in records)
        hb = next(r for r in records if r["kind"] == "flight.heartbeat")
        assert hb["interval_s"] == pytest.approx(0.05)

    def test_heartbeat_carries_counter_deltas(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), heartbeat_s_=0.05)
        metrics().counter("flight_test_total").inc(3)
        time.sleep(0.12)
        rec.close()
        beats = [r for r in load_flight_records(str(tmp_path))
                 if r["kind"] == "flight.heartbeat" and "counters" in r]
        assert beats and beats[0]["counters"]["flight_test_total"] == 3.0

    def test_segment_rotation_bounds_disk(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), segment_bytes_=300,
                             max_segments_=3, heartbeat_s_=60)
        for i in range(200):
            rec.record("chunk.done", epoch0=i, pad="x" * 40)
        rec.flush()
        rec.close()
        files = sorted(p for p in os.listdir(tmp_path)
                       if p.startswith("flight-"))
        assert rec.segments_rotated > 0
        assert len(files) <= 3
        total = sum(os.path.getsize(tmp_path / p) for p in files)
        # the cap: segments x segment size (+ one in-flight record)
        assert total <= 3 * 300 + 200
        # the ring keeps the NEWEST records: the close marker survives
        records = load_flight_records(str(tmp_path))
        assert records[-1]["kind"] == "flight.close"
        assert records[-2]["epoch0"] == 199

    def test_fresh_recorder_opens_new_segment(self, tmp_path):
        rec1 = FlightRecorder(str(tmp_path))
        rec1.record("run.start")
        rec1.close()
        rec2 = FlightRecorder(str(tmp_path))
        rec2.record("run.start")
        rec2.close()
        segs = {r["_segment"] for r in load_flight_records(str(tmp_path))}
        assert len(segs) == 2  # never appends to a possibly-torn segment

    def test_torn_tail_is_skipped_not_fatal(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        rec.record("run.start", model="X")
        rec.flush()
        rec.close()
        # simulate the write the crash interrupted
        path = tmp_path / sorted(os.listdir(tmp_path))[-1]
        with open(path, "a") as f:
            f.write('{"kind": "chunk.done", "epo')
        records = load_flight_records(str(tmp_path))
        assert [r["kind"] for r in records
                if r["kind"] != "flight.heartbeat"] == ["run.start",
                                                        "flight.close"]

    def test_record_never_raises_after_close(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        rec.close()
        rec.record("chunk.done")  # no-op, no error

    def test_tracer_spans_forward_into_flight(self, tmp_path):
        rec = FlightRecorder(str(tmp_path))
        set_flight(rec)
        try:
            with tracer().span("cache.build", kind="T"):
                pass
        finally:
            set_flight(None)
        rec.flush()
        rec.close()
        spans = [r for r in load_flight_records(str(tmp_path))
                 if r["kind"] == "span"]
        assert spans and spans[0]["name"] == "cache.build"
        assert spans[0]["attrs"]["kind"] == "T"


class TestJsonlExporterBound:
    def test_rotation_caps_disk_use(self, tmp_path):
        """The PR-6 unbounded-append hole: the exporter now rotates at
        max_bytes through the shared shift mechanism."""
        path = str(tmp_path / "telemetry.jsonl")
        exp = JsonlExporter(path, max_bytes=500, backups=2)
        for i in range(100):
            exp.write({"kind": "span", "i": i, "pad": "y" * 30})
        files = sorted(os.listdir(tmp_path))
        assert "telemetry.jsonl" in files
        assert "telemetry.jsonl.1" in files
        assert len(files) <= 3  # live + 2 backups, never more
        assert all(os.path.getsize(tmp_path / f) <= 500 + 60
                   for f in files)
        # newest record is in the live file
        with open(path) as f:
            last = json.loads(f.readlines()[-1])
        assert last["i"] == 99

    def test_survives_external_deletion(self, tmp_path):
        """Operator cleanup (or a foreign logrotate) unlinking the live
        file must not wedge the exporter: the next write recreates it."""
        path = str(tmp_path / "telemetry.jsonl")
        exp = JsonlExporter(path, max_bytes=200, backups=1)
        for i in range(10):
            exp.write({"i": i, "pad": "x" * 40})
        os.unlink(path)  # _size is still near the threshold
        for i in range(10, 20):
            exp.write({"i": i, "pad": "x" * 40})
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert lines and lines[-1]["i"] == 19

    def test_unbounded_opt_out(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        exp = JsonlExporter(path, max_bytes=0)
        for i in range(50):
            exp.write({"i": i, "pad": "z" * 100})
        assert os.listdir(tmp_path) == ["t.jsonl"]

    def test_shift_rotate_shifts_and_caps(self, tmp_path):
        path = str(tmp_path / "f")
        for content in ("one", "two", "three", "four"):
            with open(path, "w") as f:
                f.write(content)
            shift_rotate(path, backups=2)
            assert not os.path.exists(path)
        assert open(path + ".1").read() == "four"
        assert open(path + ".2").read() == "three"
        assert not os.path.exists(path + ".3")


# ---------------------------------------------------------------------------
# end-state classification (the postmortem verdicts)
# ---------------------------------------------------------------------------


def _write_segment(directory, records, index=1):
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory,
                           f"flight-{index:08d}.jsonl"), "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


class TestEndStateClassification:
    def test_clean_run(self):
        t = 1000.0
        records = [
            {"kind": "run.start", "t_wall": t, "model": "MLN"},
            {"kind": "chunk.done", "t_wall": t + 1},
            {"kind": "run.end", "t_wall": t + 2, "status": "clean"},
            {"kind": "flight.close", "t_wall": t + 3},
        ]
        assert classify_end_state(records)["end_state"] == "clean"

    def test_preempted_run(self):
        t = 1000.0
        records = [
            {"kind": "run.start", "t_wall": t},
            {"kind": "span", "name": "preemption.latch", "t_wall": t + 1},
            {"kind": "run.end", "t_wall": t + 2, "status": "stopped"},
        ]
        assert classify_end_state(records)["end_state"] == "preempted"

    def test_user_early_stop_without_latch_is_clean(self):
        """status 'stopped' is set by ANY on_chunk callback returning
        True (e.g. a convergence early-stop) — only the preemption
        latch on the timeline makes it a preemption."""
        records = [
            {"kind": "run.start", "t_wall": 1.0},
            {"kind": "run.end", "t_wall": 2.0, "status": "stopped"},
        ]
        out = classify_end_state(records)
        assert out["end_state"] == "clean"
        assert out["status"] == "stopped"

    def test_in_process_error_is_crashed(self):
        records = [
            {"kind": "run.start", "t_wall": 1.0},
            {"kind": "run.end", "t_wall": 2.0,
             "status": "error:TrainingDivergedError"},
        ]
        out = classify_end_state(records)
        assert out["end_state"] == "crashed"
        assert out["status"] == "error:TrainingDivergedError"

    def test_blocked_acquisition_before_any_run_is_wedged(self):
        """An acquisition that blocks BEFORE any run starts: the open
        grant.wait marker plus writer heartbeats marching on with no
        progress is the wedge signature, with no run.start anywhere on
        the timeline."""
        for silent_s in (300.0, 90.0):
            t = 1000.0
            records = [
                {"kind": "grant.wait", "phase": "acquire",
                 "timeout_s": silent_s, "t_wall": t},
            ] + [
                {"kind": "flight.heartbeat", "t_wall": t + i,
                 "interval_s": 1.0}
                for i in range(1, int(silent_s))
            ]
            out = classify_end_state(records)
            assert out["end_state"] == "wedged"
            assert out["evidence"]["silent_s"] >= 3.0
            assert out["evidence"]["last_progress"]["kind"] == "grant.wait"

    def test_open_grant_marker_is_wedge_even_without_silence(self):
        """The marker is written immediately before a call that can
        block forever: a timeline ENDING on it (even with few surviving
        heartbeats) reads wedged, as docs/observability.md promises."""
        records = [
            {"kind": "grant.wait", "phase": "probe", "t_wall": 1000.0},
            {"kind": "flight.heartbeat", "t_wall": 1000.5,
             "interval_s": 1.0},
        ]
        assert classify_end_state(records)["end_state"] == "wedged"

    def test_mid_run_silence_is_wedged_too(self):
        t = 1000.0
        records = [
            {"kind": "run.start", "t_wall": t},
            {"kind": "chunk.launch", "t_wall": t + 1},
        ] + [
            {"kind": "flight.heartbeat", "t_wall": t + 1 + i,
             "interval_s": 1.0} for i in range(1, 60)
        ]
        out = classify_end_state(records)
        assert out["end_state"] == "wedged"
        assert out["evidence"]["open_run"]["kind"] == "run.start"

    def test_wedge_evidence_event_wins_without_silence(self):
        t = 1000.0
        records = [
            {"kind": "run.start", "t_wall": t},
            {"kind": "chunk.launch", "t_wall": t + 1},
            {"kind": "span", "name": "watchdog.stall", "t_wall": t + 1.5,
             "attrs": {"stalled_s": 120.0}},
        ]
        assert classify_end_state(records)["end_state"] == "wedged"

    def test_abrupt_stop_is_crashed(self):
        t = 1000.0
        records = [
            {"kind": "run.start", "t_wall": t},
            {"kind": "flight.heartbeat", "t_wall": t + 0.5,
             "interval_s": 1.0},
            {"kind": "chunk.launch", "t_wall": t + 1},
        ]
        assert classify_end_state(records)["end_state"] == "crashed"

    def test_drain_evidence_classifies_drained(self):
        """An orderly close whose timeline carries ``serve.drain``
        evidence reads as a planned retirement — and outranks any
        sheds the same storm produced (the shed count stays in the
        evidence)."""
        t = 1000.0
        records = [
            {"kind": "run.start", "t_wall": t},
            {"kind": "serve.shed", "t_wall": t + 0.5, "where": "queue",
             "reason": "deadline", "criticality": "batch"},
            {"kind": "serve.drain", "t_wall": t + 1, "replica": "r1",
             "migrated": 3, "fallback_failovers": 0},
            {"kind": "run.end", "t_wall": t + 2, "status": "clean"},
            {"kind": "flight.close", "t_wall": t + 3},
        ]
        out = classify_end_state(records)
        assert out["end_state"] == "drained"
        assert out["evidence"]["n_drains"] == 1
        assert out["evidence"]["n_sheds"] == 1

    def test_shed_evidence_classifies_shed_overload(self):
        records = [
            {"kind": "run.start", "t_wall": 1000.0},
            {"kind": "serve.shed", "t_wall": 1001.0, "where": "queue",
             "reason": "deadline", "criticality": "best_effort"},
            {"kind": "run.end", "t_wall": 1002.0, "status": "clean"},
            {"kind": "flight.close", "t_wall": 1003.0},
        ]
        out = classify_end_state(records)
        assert out["end_state"] == "shed-overload"
        assert out["evidence"]["n_sheds"] == 1

    def test_no_records(self):
        assert classify_end_state([])["end_state"] == "unknown"


# ---------------------------------------------------------------------------
# bitwise parity: the recorder+ledger observe, never perturb
# ---------------------------------------------------------------------------


class TestFlightBitwiseParity:
    @pytest.mark.parametrize("make_net,make_data", [
        (_ff_net, _ff_data),
        (_rnn_net, _rnn_data),
        (_ff_graph, _ff_data),
    ], ids=["ff", "rnn", "graph"])
    def test_on_vs_off_params_bitwise(self, tmp_path, make_net,
                                      make_data, monkeypatch):
        data = make_data()
        off = make_net()
        h_off = off.fit_epochs(ListDataSetIterator(data, 12), 3,
                               chunk_epochs=1)
        rec = FlightRecorder(str(tmp_path), heartbeat_s_=10.0)
        set_flight(rec)
        monkeypatch.setenv("DL4J_FLIGHT", str(tmp_path))
        try:
            on = make_net()
            h_on = on.fit_epochs(ListDataSetIterator(data, 12), 3,
                                 chunk_epochs=1)
        finally:
            set_flight(None)
        rec.flush()
        rec.close()
        assert _leaves_equal(off.params, on.params)
        assert _leaves_equal(off.updater_state, on.updater_state)
        assert (np.asarray(h_off) == np.asarray(h_on)).all()
        kinds = [r["kind"] for r in load_flight_records(str(tmp_path))]
        assert kinds.count("run.start") == 1
        assert kinds.count("chunk.done") == 3
        assert kinds.count("run.end") == 1

    def test_spmd_wrapper_bitwise(self, tmp_path):
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs the forced multi-device host platform")
        from deeplearning4j_tpu.parallel import ParallelWrapper, build_mesh

        data = _ff_data()

        def run(recorded):
            net = _ff_net()
            wrapper = ParallelWrapper(net, mesh=build_mesh())
            cache = wrapper.build_epoch_cache(
                ListDataSetIterator(data, 12))
            assert cache is not None
            rec = None
            if recorded:
                rec = FlightRecorder(str(tmp_path), heartbeat_s_=10.0)
                set_flight(rec)
            try:
                wrapper.fit_epochs(cache, 3, chunk_epochs=1)
            finally:
                if rec is not None:
                    set_flight(None)
                    rec.close()
            return net

        off = run(False)
        on = run(True)
        assert _leaves_equal(off.params, on.params)
        assert _leaves_equal(off.updater_state, on.updater_state)


# ---------------------------------------------------------------------------
# fleet heartbeat telemetry
# ---------------------------------------------------------------------------


class TestHeartbeatPayloads:
    def test_in_memory_tracker_payload_and_compat(self):
        t = InMemoryStateTracker()
        t.heartbeat("bare")
        t.heartbeat("rich", metrics={"step_s": 0.5, "last_loss": 1.25})
        assert t.heartbeat_metrics("bare") is None
        assert t.heartbeat_metrics("rich") == {"step_s": 0.5,
                                               "last_loss": 1.25}
        assert t.heartbeat_metrics("unknown") is None
        # a payload-less beat REPLACES the payload (newest-beat
        # contract, same as the file backend) — a worker whose
        # payload_fn died must not feed stale step times to fleet_tick
        t.heartbeat("rich")
        assert t.heartbeat_metrics("rich") is None
        t.heartbeat("rich", metrics={"step_s": 0.7})
        t.evict_stale(timeout_s=0.0)
        assert t.heartbeat_metrics("rich") is None  # evicted with beat

    def test_file_tracker_payload_and_legacy_format(self, tmp_path):
        t = FileStateTracker(str(tmp_path))
        t.heartbeat("bare")
        t.heartbeat("rich", metrics={"step_s": 1.5})
        assert t.last_heartbeat("bare") is not None
        assert t.heartbeat_metrics("bare") is None
        assert t.last_heartbeat("rich") is not None
        assert t.heartbeat_metrics("rich") == {"step_s": 1.5}
        # a bare-float beat file from an old worker still parses
        with open(os.path.join(str(tmp_path), "beats", "legacy"),
                  "w") as f:
            f.write("123.5")
        assert t.last_heartbeat("legacy") == 123.5
        assert t.heartbeat_metrics("legacy") is None
        # a torn beat is absent, not an exception
        with open(os.path.join(str(tmp_path), "beats", "torn"),
                  "w") as f:
            f.write('{"t": 12')
        assert t.last_heartbeat("torn") is None

    def test_monitor_posts_payload(self):
        t = InMemoryStateTracker()
        mon = HeartbeatMonitor(t, "w0", interval_s=30.0,
                               payload_fn=lambda: {"step_s": 2.0})
        mon.start()  # first beat posts synchronously
        mon.stop()
        assert t.heartbeat_metrics("w0") == {"step_s": 2.0}

    def test_failing_payload_fn_degrades_to_bare_beat(self):
        t = InMemoryStateTracker()

        def boom():
            raise RuntimeError("telemetry must not block liveness")

        mon = HeartbeatMonitor(t, "w0", interval_s=30.0, payload_fn=boom)
        mon.start()
        mon.stop()
        assert t.last_heartbeat("w0") is not None
        assert t.heartbeat_metrics("w0") is None

    def test_legacy_tracker_without_metrics_kwarg(self):
        class LegacyTracker(StateTracker):
            def __init__(self):
                self.beats = []

            def heartbeat(self, worker_id):  # pre-payload signature
                self.beats.append(worker_id)

        t = LegacyTracker()
        mon = HeartbeatMonitor(t, "w0", interval_s=30.0,
                               payload_fn=lambda: {"step_s": 1.0})
        mon.start()
        mon.stop()
        assert t.beats == ["w0"]  # fell back, still beat


class TestFleetView:
    def _trainer(self, tracker, **kw):
        from deeplearning4j_tpu.parallel.workrouter import (
            DistributedTrainer, IterativeReduceWorkRouter)

        return DistributedTrainer(
            tracker, IterativeReduceWorkRouter(tracker),
            performer_factory=lambda: None, num_workers=3, **kw)

    def test_fleet_tick_gauges_and_straggler_flag(self):
        t = InMemoryStateTracker()
        trainer = self._trainer(t, straggler_ratio=3.0)
        t.heartbeat("w0", metrics={"step_s": 1.0, "goodput_pct": 90.0})
        t.heartbeat("w1", metrics={"step_s": 1.2, "last_loss": 0.5})
        t.heartbeat("w2", metrics={"step_s": 10.0})
        fleet = trainer.fleet_tick()
        assert set(fleet) == {"w0", "w1", "w2"}
        reg = metrics()
        assert reg.gauge("fleet_worker_step_seconds").value(
            worker="w2") == 10.0
        assert reg.gauge("fleet_worker_goodput_pct").value(
            worker="w0") == 90.0
        assert reg.gauge("fleet_worker_last_loss").value(
            worker="w1") == 0.5
        # w2 is 10x the median (1.2): flagged with evidence
        assert trainer.stragglers == {"w2"}
        assert reg.counter("fleet_stragglers_total").value(
            worker="w2") == 1.0
        assert reg.gauge("fleet_stragglers").value() == 1.0
        ev = [s for s in tracer().spans() if s.name == "fleet.straggler"]
        assert ev and ev[0].attrs["worker"] == "w2"
        assert ev[0].attrs["median_s"] == pytest.approx(1.2)
        # recovery un-flags (no repeat counter bump)
        t.heartbeat("w2", metrics={"step_s": 1.1})
        trainer.fleet_tick()
        assert trainer.stragglers == set()
        assert reg.counter("fleet_stragglers_total").value(
            worker="w2") == 1.0

    def test_no_straggler_flag_below_three_workers(self):
        t = InMemoryStateTracker()
        trainer = self._trainer(t)
        t.heartbeat("w0", metrics={"step_s": 1.0})
        t.heartbeat("w1", metrics={"step_s": 100.0})
        trainer.fleet_tick()
        assert trainer.stragglers == set()

    def test_eviction_decision_carries_evidence(self):
        t = InMemoryStateTracker()
        trainer = self._trainer(t, eviction_timeout_s=10.0)
        t.heartbeat("dead", metrics={"step_s": 4.0, "last_loss": 2.5})
        t._beats["dead"] -= 60.0  # silent for a minute
        t.heartbeat("alive", metrics={"step_s": 1.0})
        stale = trainer._evict_tick()
        assert stale == ["dead"]
        assert len(trainer.eviction_log) == 1
        decision = trainer.eviction_log[0]
        assert decision["worker"] == "dead"
        assert decision["timeout_s"] == 10.0
        assert decision["silent_s"] >= 60.0
        assert decision["last_metrics"]["last_loss"] == 2.5
        assert metrics().counter("fleet_evictions_total").value(
            worker="dead") == 1.0
        ev = [s for s in tracer().spans() if s.name == "fleet.evict"]
        assert ev and ev[0].attrs["worker"] == "dead"
        # the live worker kept its beat
        assert t.last_heartbeat("alive") is not None

    def test_end_to_end_fleet_payloads_through_training(self):
        """Workers in a real DistributedTrainer run post step-time
        payloads; the master tick aggregates them into gauges."""
        from deeplearning4j_tpu.parallel.workrouter import (
            DistributedTrainer, HogwildWorkRouter, WorkerPerformer)

        class TinyPerformer(WorkerPerformer):
            def perform(self, payload):
                # slow enough that payload-carrying beats (every 50 ms)
                # land while jobs are still flowing
                time.sleep(0.15)
                return np.ones(4, np.float32) * payload

        t = InMemoryStateTracker()
        for i in range(6):
            t.add_job(float(i))
        trainer = DistributedTrainer(
            t, HogwildWorkRouter(t),
            performer_factory=TinyPerformer, num_workers=2,
            heartbeat_interval_s=0.05)
        trainer.train(timeout_s=30.0)
        fleet = trainer.fleet_tick()
        assert fleet  # at least one worker reported a payload
        some = next(iter(fleet.values()))
        assert some["step_s"] > 0
        assert some["jobs"] >= 1
        # the in-loop (throttled) tick also ran and set the fleet gauge
        assert metrics().gauge("fleet_workers").value() >= 1.0


# ---------------------------------------------------------------------------
# crash forensics: the kill -9 chaos case
# ---------------------------------------------------------------------------

_CHAOS_CHILD = """
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

conf = (NeuralNetConfiguration.Builder().seed(0).learning_rate(0.05)
        .updater(Updater.ADAM).list()
        .layer(0, L.DenseLayer(n_in=6, n_out=12, activation="tanh"))
        .layer(1, L.OutputLayer(n_in=12, n_out=3)).build())
net = MultiLayerNetwork(conf).init()
rng = np.random.default_rng(0)
x = rng.normal(size=(48, 6)).astype(np.float32)
y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 48)]
# far more epochs than the parent lets us live: it SIGKILLs mid-chunk
net.fit_epochs(ListDataSetIterator(DataSet(x, y), 12), 10 ** 6,
               chunk_epochs=1)
"""


@pytest.mark.chaos
class TestCrashForensics:
    def test_kill9_mid_chunk_classifies_crashed(self, tmp_path):
        """The acceptance case: a REAL fused-run subprocess with
        DL4J_FLIGHT on is kill -9'd mid-chunk; flight_report must
        reconstruct the run/chunk timeline from the surviving segments
        and classify the end state as crashed."""
        flight_dir = str(tmp_path / "flight")
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   DL4J_FLIGHT=flight_dir,
                   DL4J_FLIGHT_HEARTBEAT_S="0.1")
        env.pop("DL4J_FAULTS", None)
        proc = subprocess.Popen(
            [sys.executable, "-c", _CHAOS_CHILD.format(repo=REPO)],
            env=env, cwd=REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + 120.0
            chunks = 0
            while time.monotonic() < deadline:
                chunks = sum(
                    1 for r in load_flight_records(flight_dir)
                    if r.get("kind") == "chunk.done")
                if chunks >= 3:
                    break
                assert proc.poll() is None, \
                    "fused-run child exited before the kill"
                time.sleep(0.1)
            assert chunks >= 3, "no fused chunks recorded within 120s"
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        # classification from the surviving segments alone
        report = flight_report.build_report(flight_dir)
        assert report["end_state"] == "crashed"
        assert report["n_runs_started"] == 1
        assert report["n_chunks_done"] >= 3
        kinds = {r.get("kind") for r in report["timeline"]}
        assert "chunk.done" in kinds
        # and through the CLI, machine-readably
        proc = subprocess.run(
            [sys.executable, FLIGHT_REPORT, "--json", flight_dir],
            capture_output=True, text=True, cwd=REPO)
        assert proc.returncode == 0, proc.stderr
        out = json.loads(proc.stdout)
        assert out["end_state"] == "crashed"
        assert out["n_chunks_done"] >= 3


# ---------------------------------------------------------------------------
# ledger/flight lint: chunk-boundary-only by contract
# ---------------------------------------------------------------------------


class TestLedgerFlightLint:
    def _lint(self, tmp_path, source):
        path = tmp_path / "snippet.py"
        path.write_text(textwrap.dedent(source))
        config = LintConfig(root=str(tmp_path),
                            registered_markers={"chaos", "slow"})
        return run_lint(paths=[str(path)],
                        select=["host-sync-in-hot-path"], config=config)

    def test_flight_record_in_traced_function_is_flagged(self, tmp_path):
        found = self._lint(tmp_path, """
            from deeplearning4j_tpu.analysis.annotations import traced
            from deeplearning4j_tpu.monitor.flight import flight_record

            @traced
            def step(x):
                flight_record("step", i=0)
                return x
            """)
        assert len(found) == 1
        assert "flight" in found[0].message
        assert "chunk boundaries" in found[0].message

    def test_ledger_mark_reachable_from_hot_root_is_flagged(
            self, tmp_path):
        found = self._lint(tmp_path, """
            from deeplearning4j_tpu.monitor.ledger import ledger_chunk_done

            def _epoch_run_fn(self, xs):
                return helper(xs)

            def helper(xs):
                ledger_chunk_done(epoch0=0)
                return xs
            """)
        assert len(found) == 1
        assert "ledger" in found[0].message

    def test_chunk_boundary_call_is_clean(self, tmp_path):
        found = self._lint(tmp_path, """
            from deeplearning4j_tpu.monitor.ledger import (
                ledger_chunk_done, ledger_chunk_start)

            def drive_chunks(net):
                # host-side, between dispatches: the permitted site
                ledger_chunk_start(epoch0=0)
                ledger_chunk_done(epoch0=0)
            """)
        assert found == []

    def test_shipped_tree_is_lint_clean(self):
        """The chunk driver + the new monitor modules introduce no
        findings under the extended host-sync rule."""
        config = LintConfig(root=REPO,
                            registered_markers={"chaos", "slow"})
        found = run_lint(
            paths=[os.path.join(REPO, "deeplearning4j_tpu", "perf",
                                "epoch_cache.py"),
                   os.path.join(REPO, "deeplearning4j_tpu", "monitor",
                                "ledger.py"),
                   os.path.join(REPO, "deeplearning4j_tpu", "monitor",
                                "flight.py"),
                   os.path.join(REPO, "deeplearning4j_tpu", "parallel",
                                "workrouter.py")],
            select=None, config=config)
        assert found == [], [f"{f.rule}:{f.path}:{f.line}" for f in found]


# ---------------------------------------------------------------------------
# bench_report: goodput columns + --json
# ---------------------------------------------------------------------------


def _artifact(tmp_path, name, n, value=100.0, goodput=92.5, badput=None):
    row = {
        "n": n, "rc": 0,
        "parsed": {
            "metric": "m", "value": value, "unit": "u",
            "extras": {
                "telemetry": {
                    "metrics": {}, "spans": {},
                    "ledger": {
                        "goodput_pct": goodput,
                        "badput": badput or {"cache_build": 1.5},
                    },
                },
            },
        },
    }
    path = tmp_path / name
    path.write_text(json.dumps(row))
    return str(path)


class TestBenchReportLedgerColumns:
    def test_goodput_column_in_table(self, tmp_path, capsys):
        files = [_artifact(tmp_path, "BENCH_r06.json", 6, goodput=91.0)]
        assert bench_report.main(files) == 0
        out = capsys.readouterr().out
        assert "goodput%" in out
        assert "91" in out
        assert "cache_build=1.5s" in out

    def test_json_mode_machine_readable(self, tmp_path, capsys):
        files = [
            _artifact(tmp_path, "BENCH_r06.json", 6, value=100.0),
            _artifact(tmp_path, "BENCH_r07.json", 7, value=50.0),
        ]
        rc = bench_report.main(["--json", "--check"] + files)
        out = json.loads(capsys.readouterr().out)
        assert rc == 1  # 50% drop gates, json mode included
        assert [r["round"] for r in out["rounds"]] == [6, 7]
        assert out["rounds"][0]["goodput_pct"] == 92.5
        assert out["rounds"][0]["badput"] == {"cache_build": 1.5}
        assert out["regressions"]
        assert "headline:m" in out["series"]

    def test_json_mode_clean_exit(self, tmp_path, capsys):
        files = [_artifact(tmp_path, "BENCH_r06.json", 6)]
        assert bench_report.main(["--json", "--check"] + files) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["regressions"] == []

    def test_rounds_without_a_ledger_show_no_goodput(self, tmp_path,
                                                     capsys):
        path = tmp_path / "BENCH_r03.json"
        path.write_text(json.dumps({
            "n": 3, "rc": 0,
            "parsed": {"metric": "m", "value": 1.0, "unit": "u",
                       "extras": {}}}))
        assert bench_report.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "goodput%" in out  # column exists, value is '-'
