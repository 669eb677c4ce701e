"""Telemetry subsystem tests (monitor/): registry, tracer, exporters,
the in-program metrics pack, the fused listener bus, and the control-
plane instrumentation.

The two contracts that matter most:

1. ``DL4J_TELEMETRY`` off (the default) compiles the metrics pack OUT —
   the fused program's parameters are bitwise-identical to the
   pre-telemetry (PR-5) program, asserted against the per-step reference
   replay for FF/RNN/graph.
2. Telemetry on is OBSERVATIONAL — parameters stay bitwise-identical to
   telemetry-off, and the ``[E, N, 4]`` pack values match an eager
   per-step reference to <=1e-6.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.monitor import (
    MetricsRegistry,
    SpanTracer,
    fused_metrics_stride,
    metrics,
    record_counter,
    set_tracer,
    telemetry_summary,
    tracer,
)
from deeplearning4j_tpu.monitor.exporters import (
    JsonlExporter,
    export_metrics_jsonl,
    write_prometheus_textfile,
)
from deeplearning4j_tpu.monitor.pack import METRIC_NAMES, tree_global_norm
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.train_step import loss_grads
from deeplearning4j_tpu.ops.losses import LossFunction
from deeplearning4j_tpu.perf.epoch_cache import (
    DeviceDataSetCache,
    epoch_schedule,
)

TOL = dict(rtol=1e-6, atol=1e-6)


@pytest.fixture(autouse=True)
def _fresh_global_telemetry():
    """Every test sees an empty global registry and a fresh in-memory
    tracer (no env sink), and leaves none of its state behind."""
    metrics().reset()
    set_tracer(SpanTracer())
    yield
    metrics().reset()
    set_tracer(None)


# ---------------------------------------------------------------------------
# model/data helpers (the test_epoch_cache shapes, smaller)
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
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_labels_and_values(self):
        reg = MetricsRegistry()
        c = reg.counter("dispatches_total", "help text")
        c.inc(model="MLN")
        c.inc(2, model="MLN")
        c.inc(model="CG")
        assert c.value(model="MLN") == 3
        assert c.value(model="CG") == 1
        assert c.value(model="absent") == 0
        # label order never matters
        c2 = reg.counter("multi")
        c2.inc(a="1", b="2")
        assert c2.value(b="2", a="1") == 1

    def test_counter_rejects_decrease(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("c").inc(-1)

    def test_gauge_and_histogram(self):
        reg = MetricsRegistry()
        g = reg.gauge("temp")
        g.set(3.5, zone="a")
        g.inc(0.5, zone="a")
        assert g.value(zone="a") == 4.0
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(100.0)
        v = h.value()
        assert v["count"] == 3
        assert v["sum"] == pytest.approx(100.55)
        # cumulative buckets: <=0.1 -> 1, <=1.0 -> 2, +Inf -> 3
        assert v["buckets"] == [1, 2, 3]

    def test_same_name_same_instrument(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("c", "ch").inc(model="m")
        snap = reg.snapshot()
        assert snap["c"]["type"] == "counter"
        assert snap["c"]["help"] == "ch"
        assert snap["c"]["values"] == [
            {"labels": {"model": "m"}, "value": 1.0}]
        json.dumps(snap)  # JSON-ready by contract

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("c_total").inc(3, site="a.b")
        reg.histogram("h_seconds", buckets=(1.0,)).observe(0.5)
        text = reg.to_prometheus()
        assert 'dl4j_c_total{site="a.b"} 3.0' in text
        assert 'dl4j_h_seconds_bucket{le="1.0"} 1' in text
        assert 'dl4j_h_seconds_bucket{le="+Inf"} 1' in text
        assert "dl4j_h_seconds_count 1" in text
        assert "# TYPE dl4j_c_total counter" in text

    def test_global_registry_and_record_counter(self):
        record_counter("smoke_total", 2, k="v")
        assert metrics().counter("smoke_total").value(k="v") == 2


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TestSpanTracer:
    def test_nesting_parents_and_durations(self):
        clock = FakeClock()
        t = SpanTracer(clock=clock)
        with t.span("outer", a=1) as outer:
            clock.advance(1.0)
            with t.span("inner") as inner:
                clock.advance(0.25)
            clock.advance(0.5)
            t.event("mark", b=2)
        spans = {s.name: s for s in t.spans()}
        assert spans["inner"].parent_id == outer.span_id
        assert spans["mark"].parent_id == outer.span_id
        assert spans["outer"].parent_id is None
        assert spans["inner"].duration_s == pytest.approx(0.25)
        assert spans["outer"].duration_s == pytest.approx(1.75)
        assert spans["mark"].duration_s == 0.0
        # recorded innermost-first (completion order)
        assert [s.name for s in t.spans()] == ["inner", "mark", "outer"]
        assert spans["outer"].attrs == {"a": 1}
        assert spans["outer"].start_s == pytest.approx(100.0)
        assert spans["outer"].end_s == pytest.approx(101.75)

    def test_exception_stamps_error_and_closes(self):
        t = SpanTracer(clock=FakeClock())
        with pytest.raises(RuntimeError):
            with t.span("boom"):
                raise RuntimeError("kapow")
        (sp,) = t.spans()
        assert sp.end_s is not None
        assert "RuntimeError: kapow" in sp.attrs["error"]
        assert t.current() is None  # stack unwound

    def test_capacity_bound(self):
        t = SpanTracer(capacity=4)
        for i in range(10):
            t.event(f"e{i}")
        assert [s.name for s in t.spans()] == ["e6", "e7", "e8", "e9"]

    def test_summary_aggregates(self):
        clock = FakeClock()
        t = SpanTracer(clock=clock)
        for dt in (1.0, 3.0):
            with t.span("work"):
                clock.advance(dt)
        s = t.summary(recent=1)
        assert s["n_spans"] == 2
        assert s["by_name"]["work"]["count"] == 2
        assert s["by_name"]["work"]["total_s"] == pytest.approx(4.0)
        assert s["by_name"]["work"]["max_s"] == pytest.approx(3.0)
        assert len(s["recent"]) == 1

    def test_sink_receives_span_dicts(self):
        got = []
        t = SpanTracer(clock=FakeClock(), sink=got.append)
        with t.span("x"):
            pass
        assert got and got[0]["name"] == "x"


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


class TestExporters:
    def test_jsonl_round_trip(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        ex = JsonlExporter(path)
        ex.write({"kind": "span", "name": "a"})
        ex.write({"kind": "metrics", "metrics": {"c": 1}})
        lines = [json.loads(l) for l in open(path)]
        assert [l["kind"] for l in lines] == ["span", "metrics"]
        assert lines[1]["metrics"] == {"c": 1}

    def test_env_dir_wires_span_sink_and_metrics_export(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("DL4J_TELEMETRY_DIR", str(tmp_path))
        set_tracer(None)  # rebuild the global tracer with the env sink
        with tracer().span("wired"):
            pass
        record_counter("exported_total")
        export_metrics_jsonl()
        lines = [json.loads(l)
                 for l in open(tmp_path / "telemetry.jsonl")]
        kinds = [l["kind"] for l in lines]
        assert "span" in kinds and "metrics" in kinds
        span_line = next(l for l in lines if l["kind"] == "span")
        assert span_line["name"] == "wired"
        assert "t_wall" in span_line
        m = next(l for l in lines if l["kind"] == "metrics")
        assert m["metrics"]["exported_total"]["values"][0]["value"] == 1

    def test_prometheus_textfile_round_trip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("rt_total").inc(5)
        path = write_prometheus_textfile(reg, str(tmp_path / "m.prom"))
        text = open(path).read()
        assert "dl4j_rt_total 5.0" in text

    def test_prometheus_default_path_needs_env(self, monkeypatch):
        monkeypatch.delenv("DL4J_TELEMETRY_DIR", raising=False)
        assert write_prometheus_textfile(MetricsRegistry()) is None

    def test_telemetry_summary_block(self):
        record_counter("sum_total")
        with tracer().span("sum.span"):
            pass
        block = telemetry_summary()
        assert "sum_total" in block["metrics"]
        assert block["spans"]["by_name"]["sum.span"]["count"] == 1
        json.dumps(block)


# ---------------------------------------------------------------------------
# env resolution
# ---------------------------------------------------------------------------


class TestEnvResolution:
    def test_default_off(self, monkeypatch):
        monkeypatch.delenv("DL4J_TELEMETRY", raising=False)
        assert fused_metrics_stride() == 0

    def test_env_on_with_stride(self, monkeypatch):
        monkeypatch.setenv("DL4J_TELEMETRY", "on")
        monkeypatch.setenv("DL4J_TELEMETRY_STRIDE", "3")
        assert fused_metrics_stride() == 3
        assert fused_metrics_stride(False) == 0  # explicit override wins
        assert fused_metrics_stride(1) == 1

    def test_overrides(self):
        assert fused_metrics_stride(True) == 1
        assert fused_metrics_stride(7) == 7
        assert fused_metrics_stride(0) == 0

    def test_env_engages_fused_pack(self, monkeypatch):
        monkeypatch.setenv("DL4J_TELEMETRY", "on")
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 1)
        assert net._last_metrics is not None
        assert np.asarray(net._last_metrics).shape == (1, 4, 4)


# ---------------------------------------------------------------------------
# fused-path parity (the acceptance contract)
# ---------------------------------------------------------------------------


def _fused_params(make_net, data, epochs, batch, telemetry, **kw):
    net = make_net()
    hist = net.fit_epochs(ListDataSetIterator(data, batch), epochs,
                          telemetry=telemetry, **kw)
    return net, hist


class TestFusedTelemetryParity:
    @pytest.mark.parametrize("make_net,make_data", [
        (_ff_net, _ff_data),
        (_rnn_net, _rnn_data),
        (_ff_graph, _ff_data),
    ], ids=["ff", "rnn", "graph"])
    def test_on_vs_off_params_bitwise(self, make_net, make_data):
        """The pack is observational: compiling it in changes NOTHING
        about training — params and loss history bitwise-equal."""
        data = make_data()
        off, h_off = _fused_params(make_net, data, 3, 12, telemetry=False)
        on, h_on = _fused_params(make_net, data, 3, 12, telemetry=1)
        assert _leaves_equal(off.params, on.params)
        assert _leaves_equal(off.updater_state, on.updater_state)
        assert (np.asarray(h_off) == np.asarray(h_on)).all()
        assert off._last_metrics is None
        assert np.asarray(on._last_metrics).shape == (
            3, h_on.shape[1], len(METRIC_NAMES))
        assert np.isfinite(np.asarray(on._last_metrics)).all()

    def test_off_bitwise_vs_per_step_reference(self):
        """telemetry=off IS the PR-5 program: fused run vs the per-step
        train program driven on the identical key stream — bitwise."""
        fused = _ff_net()
        ref = _ff_net()
        data = _ff_data(96)
        hist = fused.fit_epochs(ListDataSetIterator(data, 24), 3,
                                telemetry=False, guard="off")
        cache = DeviceDataSetCache.build(ListDataSetIterator(data, 24))
        keys = jax.random.split(ref._rng, 4)
        ref._rng = keys[0]
        it = 0
        for ekey in keys[1:]:
            order, skeys = epoch_schedule(ekey, cache.n_batches, True)
            order = np.asarray(order)
            for j in range(cache.n_batches):
                i = int(order[j])
                (ref.params, ref.updater_state, ref.net_state, *_) = (
                    ref._train_step(
                        ref.params, ref.updater_state, ref.net_state,
                        jnp.asarray(it, jnp.int32),
                        jnp.asarray(1.0, jnp.float32),
                        (cache.features[i], cache.labels[i], None,
                         cache.labels_mask[i]), skeys[j]))
                it += 1
        assert _leaves_equal(fused.params, ref.params)
        assert np.isfinite(np.asarray(hist)).all()

    def test_guard_and_telemetry_compose(self):
        """Both sentinel and pack compiled in: both histories come back,
        and a poisoned batch shows trip semantics in the pack — zero
        update norm, unchanged param norm, non-finite grad norm."""
        data = _ff_data(48)
        x = np.asarray(data.features).copy()
        x[12:24] = np.nan  # batch #1 (shuffle=False -> step 1)
        poisoned = DataSet(x, data.labels)
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(poisoned, 12), 2,
                       shuffle=False, guard="skip", telemetry=1)
        trips = np.asarray(net._last_sentinel)
        mets = np.asarray(net._last_metrics)
        assert trips.shape == (2, 4) and trips[:, 1].all()
        assert not trips[:, 0].any()
        # tripped step: no update applied
        assert mets[0, 1, 1] == 0.0
        assert mets[0, 1, 2] == mets[0, 0, 2]  # param norm carried
        assert not np.isfinite(mets[0, 1, 0])  # the poisoned grad norm
        # healthy steps stay fully finite
        assert np.isfinite(mets[:, [0, 2, 3], :]).all()

    def test_stride_gates_with_nan_rows(self):
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 2,
                       telemetry=2, guard="off")
        m = np.asarray(net._last_metrics)
        measured = np.isfinite(m[:, :, 0]).reshape(-1)
        # iterations 0..7, stride 2 -> even iterations measured
        assert list(measured) == [i % 2 == 0 for i in range(8)]

    def test_program_cache_keyed_on_stride(self):
        net = _ff_net()
        it = lambda: ListDataSetIterator(_ff_data(), 12)
        net.fit_epochs(it(), 1)
        net.fit_epochs(it(), 1, telemetry=1)
        net.fit_epochs(it(), 1, telemetry=2)
        assert {k[3] for k in net._epoch_steps} == {0, 1, 2}


# ---------------------------------------------------------------------------
# metrics-pack values vs an eager per-step reference
# ---------------------------------------------------------------------------


class TestMetricsPackValues:
    def test_values_match_eager_reference(self):
        """Fused [E, N, 4] pack vs eagerly recomputed norms on the same
        key stream: <=1e-6."""
        epochs, batch = 2, 12
        data = _ff_data(48)
        net = _ff_net()
        rng0 = net._rng
        net.fit_epochs(ListDataSetIterator(data, batch), epochs,
                       telemetry=1, guard="off")
        fused = np.asarray(net._last_metrics)

        ref = _ff_net()
        cache = DeviceDataSetCache.build(ListDataSetIterator(data, batch))
        keys = jax.random.split(rng0, epochs + 1)
        it = 0
        expect = np.zeros_like(fused)
        for e, ekey in enumerate(keys[1:]):
            order, skeys = epoch_schedule(ekey, cache.n_batches, True)
            order = np.asarray(order)
            for j in range(cache.n_batches):
                i = int(order[j])
                (_, (nst2, _)), grads = loss_grads(
                    ref, ref.params, ref.net_state,
                    (cache.features[i], cache.labels[i], None,
                     cache.labels_mask[i]), skeys[j])
                it_arr = jnp.asarray(it, jnp.int32)
                one = jnp.asarray(1.0, jnp.float32)
                new_params, new_upd = ref._apply_updaters(
                    ref.params, ref.updater_state, grads, it_arr, one)
                upd = jax.tree_util.tree_map(
                    lambda a, b: (a.astype(jnp.float32)
                                  - b.astype(jnp.float32)),
                    new_params, ref.params)
                expect[e, j] = [
                    float(tree_global_norm(grads)),
                    float(tree_global_norm(upd)),
                    float(tree_global_norm(new_params)),
                    float(ref._lr_scale(it_arr, one)),
                ]
                ref.params, ref.updater_state, ref.net_state = (
                    new_params, new_upd, nst2)
                it += 1
        np.testing.assert_allclose(fused, expect, **TOL)

    def test_graph_pack_values_sane(self):
        """ComputationGraph pack: finite norms, positive once training
        moves, lr_scale column == 1 under the default flat policy."""
        net = _ff_graph()
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 2,
                       telemetry=1, guard="off")
        m = np.asarray(net._last_metrics)
        assert np.isfinite(m).all()
        assert (m[:, :, 0] > 0).all()  # grad norms
        assert (m[:, :, 1] > 0).all()  # update norms
        np.testing.assert_allclose(m[:, :, 3], 1.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# the fused listener bus
# ---------------------------------------------------------------------------


class TestListenerBus:
    def test_score_listener_exact_iteration_numbering(self):
        from deeplearning4j_tpu.optimize.listeners import (
            ScoreIterationListener)

        lines = []
        net = _ff_net()
        net.set_listeners(ScoreIterationListener(3, printer=lines.append))
        hist = net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 3)
        # 12 steps, stride 3 -> iterations 3, 6, 9, 12
        assert len(lines) == 4
        flat = np.asarray(hist).reshape(-1)
        for line, it in zip(lines, (3, 6, 9, 12)):
            assert f"iteration {it} " in line
            assert f"{float(flat[it - 1])}" in line

    def test_numbering_continues_across_runs_and_resume(self):
        from deeplearning4j_tpu.optimize.listeners import (
            CollectScoresIterationListener)

        net = _ff_net()
        coll = CollectScoresIterationListener()
        net.set_listeners(coll)
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 1)
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 1)
        assert [i for i, _ in coll.scores] == list(range(1, 9))

    def test_chunk_done_receives_metrics_history(self):
        got = {}

        class Capture:
            def iteration_done(self, model, iteration):
                pass

            def chunk_done(self, model, iteration0, losses, metrics=None):
                got.setdefault("calls", []).append(
                    (iteration0, np.asarray(losses).shape,
                     None if metrics is None
                     else np.asarray(metrics).shape))

        net = _ff_net()
        net.set_listeners(Capture())
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 2,
                       telemetry=1)
        # listeners attached -> chunk = 1 epoch -> two chunk_done calls
        assert got["calls"] == [(0, (1, 4), (1, 4, 4)),
                                (4, (1, 4), (1, 4, 4))]

    def test_legacy_listener_still_fires_per_chunk(self):
        class Legacy:  # no chunk_done, not an IterationListener
            def __init__(self):
                self.fired = []

            def iteration_done(self, model, iteration):
                self.fired.append(iteration)

        net = _ff_net()
        legacy = Legacy()
        net.set_listeners(legacy)
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 3)
        assert legacy.fired == [4, 8, 12]  # once per 1-epoch chunk

    def test_ui_histogram_listener_posts_loss_history(self):
        from deeplearning4j_tpu.ui.listeners import (
            HistogramIterationListener)

        posts = []

        class FakeServer:
            def post_update(self, kind, payload, sid=None):
                posts.append((kind, payload))

        net = _ff_net()
        net.set_listeners(HistogramIterationListener(server=FakeServer()))
        hist = net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 2,
                              telemetry=1)
        assert len(posts) == 2
        kind, payload = posts[0]
        assert kind == "weights"
        lh = payload["loss_history"]
        assert lh["iterations"] == [1, 2, 3, 4]
        np.testing.assert_allclose(
            lh["losses"], np.asarray(hist)[0], rtol=1e-6)
        mp = payload["metrics_pack"]
        for name in METRIC_NAMES:
            assert len(mp[name]) == 4
        assert "parameters" in payload


# ---------------------------------------------------------------------------
# control-plane instrumentation
# ---------------------------------------------------------------------------


class TestInstrumentation:
    def test_chunk_dispatch_counter_and_span(self):
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(_ff_data(), 12), 2,
                       chunk_epochs=1)
        assert metrics().counter("train_chunk_dispatches_total").value(
            model="MultiLayerNetwork") == 2
        chunk_spans = [s for s in tracer().spans()
                       if s.name == "epoch.chunk"]
        assert len(chunk_spans) == 2
        assert chunk_spans[0].attrs["steps"] == 4
        build_spans = [s for s in tracer().spans()
                       if s.name == "cache.build"]
        assert build_spans and build_spans[0].attrs["cached"] is True

    def test_per_step_dispatch_counter_mirrors_attribute(self):
        net = _ff_net()
        net.fit(_ff_data(12))
        assert net._train_dispatches == 1
        assert metrics().counter("train_dispatches_total").value(
            model="MultiLayerNetwork", path="per_step") == 1

    def test_eval_readback_counter(self):
        net = _ff_net()
        net.evaluate(_ff_data(16))
        assert metrics().counter("eval_readbacks_total").value(
            model="MultiLayerNetwork", kind="confusion") == 1

    def test_retry_counter_and_sleep_span(self):
        from deeplearning4j_tpu.resilience import RetryPolicy

        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "ok"

        policy = RetryPolicy(max_attempts=5, base_delay_s=0.01,
                             sleep=lambda s: None, seed=0)
        assert policy.call(flaky) == "ok"
        assert metrics().counter("retry_attempts_total").value(
            fn="flaky") == 2
        sleeps = [s for s in tracer().spans() if s.name == "retry.sleep"]
        assert [s.attrs["attempt"] for s in sleeps] == [1, 2]

    def test_watchdog_stall_counter_and_event(self):
        import time as _time

        from deeplearning4j_tpu.resilience.watchdog import StepWatchdog

        stalls = []
        with StepWatchdog(0.05, on_stall=stalls.append, poll_s=0.01):
            _time.sleep(0.3)
        assert stalls
        assert metrics().counter("watchdog_stalls_total").value() >= 1
        assert any(s.name == "watchdog.stall" for s in tracer().spans())

    @pytest.mark.chaos
    def test_fault_site_fire_counter(self):
        from deeplearning4j_tpu.resilience import faults

        with faults.inject("telemetry.test", faults.fail_nth(1)):
            with pytest.raises(faults.FaultInjected):
                faults.fault_point("telemetry.test")
            faults.fault_point("telemetry.test")
        c = metrics().counter("fault_site_fires_total")
        assert c.value(site="telemetry.test", raised="true") == 1
        assert c.value(site="telemetry.test", raised="false") == 1

    def test_preemption_latch_counter(self):
        from deeplearning4j_tpu.resilience.preemption import (
            PreemptionGuard)

        guard = PreemptionGuard(signals=())
        guard.request()
        assert guard.check()
        assert metrics().counter("preemption_latches_total").value(
            source="request") == 1
        assert any(s.name == "preemption.latch"
                   for s in tracer().spans())

    def test_checkpoint_write_latency_and_spans(self, tmp_path):
        from deeplearning4j_tpu.parallel.cluster import (
            FaultTolerantTrainer)

        net = _ff_net()
        net.fit(_ff_data(12))
        trainer = FaultTolerantTrainer(net, str(tmp_path))
        trainer.save()
        hist = metrics().histogram("checkpoint_write_seconds").value()
        assert hist["count"] == 1 and hist["sum"] > 0
        assert metrics().counter("checkpoint_saves_total").value() == 1
        names = {s.name for s in tracer().spans()}
        assert "checkpoint.write" in names
        assert trainer.resume() is True
        assert "checkpoint.resume" in {s.name for s in tracer().spans()}
        assert metrics().counter("checkpoint_resumes_total").value(
            outcome="restored") == 1

    def test_save_async_snapshot_histogram(self, tmp_path):
        from deeplearning4j_tpu.parallel.cluster import (
            FaultTolerantTrainer)

        net = _ff_net()
        net.fit(_ff_data(12))
        trainer = FaultTolerantTrainer(net, str(tmp_path))
        trainer.save_async().result()
        trainer.wait_for_saves()
        snap = metrics().histogram("checkpoint_snapshot_seconds").value()
        assert snap["count"] == 1


# ---------------------------------------------------------------------------
# SPMD wrapper
# ---------------------------------------------------------------------------


class TestWrapperTelemetry:
    def test_sharded_pack_matches_single_device(self):
        from deeplearning4j_tpu.parallel import ParallelWrapper, build_mesh

        data = _ff_data(96)
        single = _ff_net()
        single.fit_epochs(ListDataSetIterator(data, 24), 2, telemetry=1)
        net = _ff_net()
        wrapper = ParallelWrapper(net, mesh=build_mesh())
        hist = wrapper.fit_epochs(ListDataSetIterator(data, 24), 2,
                                  telemetry=1)
        assert hist is not None
        assert net._train_dispatches == 1  # still one SPMD dispatch
        m = np.asarray(net._last_metrics)
        assert m.shape == (2, 4, len(METRIC_NAMES))
        # all-reduce order only: <=1e-5 vs the single-device pack
        np.testing.assert_allclose(
            m, np.asarray(single._last_metrics), rtol=1e-5, atol=1e-5)
        assert (True, 1, True, 1) in wrapper._epoch_steps


# The no-bare-counters invariant now lives in dl4j-lint's bare-counter
# rule: tests/test_analysis.py::TestBareCounterRule subprocess-runs the
# CLI (and asserts the old scripts/lint_telemetry.py is gone);
# scripts/verify.sh --obs runs `dl4j_lint.py --select bare-counter`
# directly. No duplicate whole-tree scan here.
