"""The program's spans on the profiler's clock (PR 23): the tracer's mirror
into ``jax.profiler.TraceAnnotation``, ``SpanTracer.record``, the sink list
the flight recorder registers in, and the phase spans of ``DecodeServer.step``
and ``TransformerLM.fit_batch``.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import TransformerLM
from deeplearning4j_tpu.monitor import SpanTracer, set_tracer, tracer
from deeplearning4j_tpu.monitor import trace as trace_mod
from deeplearning4j_tpu.serving import DecodeServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TickClock:
    """Every read advances, so every span has a length and an order."""

    def __init__(self, tick=0.01):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


def _fake_annotation(log):
    class Annotation:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            log.append(("enter", self.name, self.kwargs))

        def __exit__(self, *exc):
            log.append(("exit", self.name, exc))

    return Annotation


@pytest.fixture()
def annotations(monkeypatch):
    log = []
    monkeypatch.setattr(trace_mod, "_ANNOTATION", _fake_annotation(log))
    return log


@pytest.fixture()
def fresh_tracer():
    clock = TickClock()
    t = SpanTracer(clock=clock)
    set_tracer(t)
    yield t, clock
    set_tracer(None)


# ---------------------------------------------------------------------------
# the mirror
# ---------------------------------------------------------------------------
class TestProfilerMirror:
    def test_enters_and_leaves_in_lifo_order_with_scalar_attrs(
            self, annotations):
        t = SpanTracer()
        with t.span("outer", n=3, kind="plain", ratio=0.5, flag=True,
                    arr=np.zeros(2), nothing=None) as sp:
            sp.attrs["late"] = 1          # after entry: the ring only
            with t.span("inner"):
                pass
        assert [(a, n) for a, n, _ in annotations] == [
            ("enter", "dl4j.outer"), ("enter", "dl4j.inner"),
            ("exit", "dl4j.inner"), ("exit", "dl4j.outer")]
        assert annotations[0][2] == {"n": 3, "kind": "plain", "ratio": 0.5,
                                     "flag": True}
        assert annotations[1][2] == {}
        assert t.spans()[-1].attrs["late"] == 1

    def test_survives_an_exception_in_the_body(self, annotations):
        t = SpanTracer()
        with pytest.raises(KeyError):
            with t.span("boom"):
                raise KeyError("x")
        assert [a for a, _, _ in annotations] == ["enter", "exit"]
        (sp,) = t.spans()
        assert sp.attrs["error"].startswith("KeyError") and sp.end_s
        assert t.current() is None

    def test_events_and_records_are_not_mirrored(self, annotations):
        t = SpanTracer()
        t.event("watchdog.stall")
        t.record("serve.queued", 1.0, 2.0, request=7)
        assert annotations == []
        assert [s.name for s in t.spans()] == ["watchdog.stall",
                                               "serve.queued"]

    def test_binds_the_real_class_once_jax_is_imported(self, monkeypatch):
        import jax

        monkeypatch.setattr(trace_mod, "_ANNOTATION", None)
        with SpanTracer().span("real", step=1):
            pass        # no profiler session: the annotation is inert
        assert trace_mod._ANNOTATION is jax.profiler.TraceAnnotation

    def test_no_op_and_no_jax_when_only_monitor_is_imported(self):
        code = (
            "import sys\n"
            "import deeplearning4j_tpu.monitor as m\n"
            "with m.tracer().span('a', n=1):\n"
            "    with m.tracer().span('b'):\n"
            "        pass\n"
            "from deeplearning4j_tpu.monitor import trace\n"
            "assert 'jax' not in sys.modules, 'monitor pulled in jax'\n"
            "assert trace._ANNOTATION is None\n"
            "print([s.name for s in m.tracer().spans()])\n")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("DL4J_")}
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.strip() == "['b', 'a']"


# ---------------------------------------------------------------------------
# record() and the sinks
# ---------------------------------------------------------------------------
class TestRecordAndSinks:
    def test_record_lands_in_ring_and_sink_with_the_given_times(self):
        got = []
        t = SpanTracer(clock=TickClock(), sink=got.append)
        with t.span("serve.step") as step:
            sp = t.record("serve.queued", 12.5, 14.0, request=9,
                          criticality="batch")
        assert (sp.start_s, sp.end_s, sp.duration_s) == (12.5, 14.0, 1.5)
        assert sp.parent_id == step.span_id
        assert t.spans()[0] is sp
        assert got[0]["name"] == "serve.queued"
        assert (got[0]["start_s"], got[0]["end_s"]) == (12.5, 14.0)
        assert got[0]["attrs"] == {"request": 9, "criticality": "batch"}

    def test_process_sinks_get_every_tracers_spans_until_removed(self):
        got, own = [], []
        trace_mod.add_sink(got.append)
        trace_mod.add_sink(got.append)          # twice is once
        try:
            with SpanTracer().span("one"):
                pass
            with SpanTracer(sink=own.append).span("two"):
                pass
        finally:
            trace_mod.remove_sink(got.append)
        with SpanTracer().span("three"):
            pass
        assert [d["name"] for d in got] == ["one", "two"]
        assert [d["name"] for d in own] == ["two"]
        assert got.append not in trace_mod._SINKS

    def test_a_failing_sink_neither_raises_nor_starves_the_next(self):
        got = []

        def broken(_):
            raise OSError("disk full")

        trace_mod.add_sink(got.append)
        try:
            with SpanTracer(sink=broken).span("kept"):
                pass
        finally:
            trace_mod.remove_sink(got.append)
        assert [d["name"] for d in got] == ["kept"]

    def test_set_flight_registers_and_unregisters_the_recorder(
            self, tmp_path):
        from deeplearning4j_tpu.monitor import FlightRecorder, set_flight

        a = FlightRecorder(str(tmp_path / "a"), heartbeat_s_=10.0)
        b = FlightRecorder(str(tmp_path / "b"), heartbeat_s_=10.0)
        try:
            set_flight(a)
            assert trace_mod._SINKS.count(a.record_span) == 1
            set_flight(b)               # replaces, never stacks
            assert a.record_span not in trace_mod._SINKS
            assert b.record_span in trace_mod._SINKS
        finally:
            set_flight(None)
            a.close()
            b.close()
        assert b.record_span not in trace_mod._SINKS


# ---------------------------------------------------------------------------
# the serve step's phases
# ---------------------------------------------------------------------------
def _tiny_lm():
    return TransformerLM(vocab_size=61, d_model=32, num_heads=4,
                         num_kv_heads=2, num_layers=2, max_len=96,
                         seed=3).init()


class TestServeSpans:
    @pytest.fixture()
    def served(self, fresh_tracer):
        """One request of 3 tokens through a 2-slot server whose clock is
        the tracer's. The plain loop reads one step behind: step 1 admits
        and dispatches block 1, step 2 dispatches block 2 and books block
        1, step 3 owes nothing, books block 2 and retires, step 4 finds
        nothing."""
        t, clock = fresh_tracer
        server = DecodeServer(_tiny_lm(), slots=2, max_len=96, clock=clock)
        req = server.submit(np.arange(1, 20, dtype=np.int32), 3)
        progressed = [server.step() for _ in range(4)]
        return t, server, req, progressed

    def test_one_request_is_queued_prefilled_and_retired_under_one_id(
            self, served):
        t, _server, req, progressed = served
        assert progressed == [True, True, True, False]
        mine = [s for s in t.spans() if s.attrs.get("request") == req.id]
        by_start = sorted(mine, key=lambda s: s.start_s)
        # the first token is read behind the step's decode dispatch, in
        # the step that admitted (ISSUE 48): a span of its own
        assert [s.name for s in by_start] == ["serve.queued",
                                              "serve.request",
                                              "serve.prefill",
                                              "serve.first_token"]
        queued, request, prefill, token = by_start
        assert queued.start_s == request.start_s == req.submit_s
        assert queued.end_s <= prefill.start_s <= prefill.end_s
        assert request.end_s == req.finish_s
        assert prefill.end_s < token.start_s < req.first_token_s \
            <= token.end_s
        assert token.attrs == {"request": req.id, "slot": req.slot,
                               "ahead": 1}
        assert prefill.attrs["prompt_len"] == 19
        assert prefill.attrs["bucket"] == 32
        assert prefill.attrs["slot"] == req.slot == request.attrs["slot"]
        assert prefill.attrs["queue_wait_us"] == int(
            1e6 * queued.duration_s)
        assert queued.attrs["criticality"] == "interactive"
        assert request.attrs["tokens"] == 3 == len(req.tokens)

    def test_phases_are_children_of_their_step(self, served):
        t, _server, _req, _ = served
        spans = t.spans()
        steps = [s for s in spans if s.name == "serve.step"]
        assert len(steps) == 4

        def children(step):
            return sorted((s for s in spans if s.parent_id == step.span_id),
                          key=lambda s: s.start_s)

        # the first dispatch after idling has no block behind it to book;
        # the admission's first token is read behind it
        assert [s.name for s in children(steps[0])] == [
            "serve.admit", "serve.decode", "serve.first_token"]
        # nothing waits at the later boundaries: no serve.admit at all
        for step in steps[1:3]:
            assert [s.name for s in children(step)] == [
                "serve.decode", "serve.emit"]
        assert children(steps[3]) == []
        admit = children(steps[0])[0]
        assert admit.attrs["n"] == 1
        assert {s.name for s in spans if s.parent_id == admit.span_id} == {
            "serve.queued", "serve.prefill"}
        assert steps[0].attrs == {"admitted": 1, "live": 1}
        assert steps[1].attrs == {"admitted": 0, "live": 1}
        assert steps[2].attrs == {"admitted": 0, "live": 0}
        assert steps[3].attrs == {"admitted": 0}

    def test_decode_and_emit_attrs(self, served):
        t, server, _req, _ = served
        decodes = [s for s in t.spans() if s.name == "serve.decode"]
        emits = [s for s in t.spans() if s.name == "serve.emit"]
        assert [d.attrs for d in decodes] == [
            {"live": 1, "kind": "plain", "ahead": 0, "kv_rows": 40},
            {"live": 1, "kind": "plain", "ahead": 1, "kv_rows": 42},
            {"live": 0, "kind": "plain", "ahead": 0}]
        assert [e.attrs for e in emits] == [
            {"tokens": 1, "retired": 0}, {"tokens": 1, "retired": 1}]
        assert server.steps == 2 and server.decode_tokens == 2
        retire = next(s for s in t.spans() if s.name == "serve.request")
        assert retire.parent_id == emits[1].span_id

    def test_spans_a_step_stay_inside_the_budget(self, served):
        """At most 4 spans a step and 4 a request (ISSUE 23;
        ``serve.first_token``: ISSUE 48)."""
        t, _server, _req, _ = served
        names = [s.name for s in t.spans()]
        assert len(names) == 4 + 1 + 3 + 2 + 4
        assert names.count("serve.first_token") == 1
        assert names.count("serve.admit") == 1

    def test_kind_and_mirror_carry_the_request(self, fresh_tracer,
                                               annotations):
        t, clock = fresh_tracer
        server = DecodeServer(_tiny_lm(), slots=2, max_len=96, clock=clock)
        req = server.submit(np.arange(1, 9, dtype=np.int32), 3)
        server.drain()
        entered = {n: kw for a, n, kw in annotations if a == "enter"}
        # two tokens after the prompt's: two dispatches, the second
        # ahead of the first's read, and a span that only reads
        assert [kw for a, n, kw in annotations
                if a == "enter" and n == "dl4j.serve.decode"] == [
            {"live": 1, "kind": "plain", "ahead": 0, "kv_rows": 18},
            {"live": 1, "kind": "plain", "ahead": 1, "kv_rows": 20},
            {"live": 0, "kind": "plain", "ahead": 0}]
        assert entered["dl4j.serve.prefill"]["request"] == req.id
        assert set(entered["dl4j.serve.prefill"]) == {
            "request", "slot", "prompt_len", "bucket", "queue_wait_us"}
        assert "dl4j.serve.queued" not in entered   # record(): ring only
        assert "dl4j.serve.request" not in entered


# ---------------------------------------------------------------------------
# the LM step
# ---------------------------------------------------------------------------
class TestTrainSpans:
    @pytest.fixture()
    def lm(self):
        return TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                             num_layers=1, max_len=16, seed=0).init()

    def test_blocking_step_holds_the_sync(self, lm, fresh_tracer):
        t, _ = fresh_tracer
        tokens = np.arange(16, dtype=np.int32).reshape(2, 8) % 32
        loss = lm.fit_batch(tokens)
        assert isinstance(loss, float)
        step, = [s for s in t.spans() if s.name == "train.step"]
        sync, = [s for s in t.spans() if s.name == "train.sync"]
        assert sync.parent_id == step.span_id
        assert step.start_s < sync.start_s < sync.end_s < step.end_s
        assert step.attrs == {"step": 0} and sync.attrs == {}
        lm.fit_batch(tokens)
        assert [s.attrs["step"] for s in t.spans()
                if s.name == "train.step"] == [0, 1]

    def test_non_blocking_step_has_no_sync(self, lm, fresh_tracer):
        t, _ = fresh_tracer
        tokens = np.arange(16, dtype=np.int32).reshape(2, 8) % 32
        loss = lm.fit_batch(tokens, block=False)
        assert not isinstance(loss, float)
        assert [s.name for s in t.spans()] == ["train.step"]
