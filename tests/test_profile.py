"""Compiled-program performance observatory tests (monitor/profile +
monitor/memory + scripts/bench_report.py).

The contracts that matter:

1. ``DL4J_PROFILE`` off (the default) leaves the fused path untouched —
   trained params are BITWISE identical to the profile-on run for
   FF/RNN/graph and the SPMD wrapper (profiling changes when the numbers
   are read, never what runs).
2. With it on, every cached ``_epoch_steps`` key carries a
   ProgramProfile with nonzero cost-analysis FLOPs and a
   memory-analysis peak, and cost-analysis FLOPs agree with the
   analytic formula on a known GEMM.
3. The epoch-cache per-shard HBM budget model matches the bytes the
   devices actually hold (``validate_cache_budget``), and watermarks
   sample at chunk boundaries only.
4. ``bench_report.py`` flags wedge/error rounds, never scores them, and
   exits nonzero on an injected regression.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.analysis.engine import LintConfig, run_lint
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.monitor import (
    MetricsRegistry,
    SpanTracer,
    metrics,
    set_tracer,
    tracer,
)
from deeplearning4j_tpu.monitor.memory import (
    cache_resident_bytes,
    live_array_bytes,
    sample_hbm_watermark,
    validate_cache_budget,
)
from deeplearning4j_tpu.monitor.profile import (
    ProfiledProgram,
    ProfileStore,
    capture_program_profile,
    classify_boundedness,
    flops_divergence_pct,
    profile_enabled,
    profiles,
)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.losses import LossFunction
from deeplearning4j_tpu.perf.epoch_cache import DeviceDataSetCache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench_report():
    spec = importlib.util.spec_from_file_location(
        "bench_report", os.path.join(REPO, "scripts", "bench_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench_report = _load_bench_report()


@pytest.fixture(autouse=True)
def _fresh_observability(monkeypatch):
    """Every test sees an empty registry/tracer/profile store and the
    DL4J_PROFILE default (off); nothing leaks out."""
    monkeypatch.delenv("DL4J_PROFILE", raising=False)
    metrics().reset()
    profiles().reset()
    set_tracer(SpanTracer())
    yield
    metrics().reset()
    profiles().reset()
    set_tracer(None)


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


def _bitwise_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(la, lb))


MAKERS = {
    "ff": (_ff_net, lambda: _ff_data(48)),
    "rnn": (_rnn_net, lambda: _rnn_data(24)),
    "graph": (_ff_graph, lambda: _ff_data(48)),
}


# ---------------------------------------------------------------------------
# capture_program_profile
# ---------------------------------------------------------------------------


class TestCaptureProgramProfile:
    def test_gemm_flops_agree_with_analytic(self):
        """cost-analysis FLOPs vs the textbook 2*n^3 on a known GEMM —
        the cross-check that anchors every cost-derived MFU number."""
        n = 256
        f = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((n, n), jnp.float32)
        prof, compiled = capture_program_profile(
            f, (a, a), name="gemm", key=("test",))
        analytic = 2.0 * n ** 3
        assert prof.flops is not None and prof.flops > 0
        div = flops_divergence_pct(analytic, prof.flops)
        assert abs(div) < 5.0, f"GEMM flops diverged {div}%"
        # the returned executable computes the same thing
        out = compiled(a, a)
        assert np.allclose(np.asarray(out), np.asarray(f(a, a)))

    def test_memory_analysis_peak_nonzero(self):
        f = jax.jit(lambda a: a * 2.0)
        a = jnp.ones((64, 64), jnp.float32)
        prof, _ = capture_program_profile(f, (a,), name="mul",
                                          key=("test",))
        assert prof.argument_bytes and prof.argument_bytes >= a.nbytes
        assert prof.output_bytes and prof.output_bytes >= a.nbytes
        assert prof.peak_bytes and prof.peak_bytes > 0
        assert prof.compile_s is not None and prof.compile_s > 0
        assert prof.lower_s is not None

    def test_registry_and_span_mirror(self):
        f = jax.jit(lambda a: a + 1)
        capture_program_profile(f, (jnp.ones(8),), name="inc",
                                key=(1, 2))
        snap = metrics().snapshot()
        assert "program_flops" in snap
        assert "program_peak_hbm_bytes" in snap
        assert "program_compile_seconds" in snap
        labels = snap["program_flops"]["values"][0]["labels"]
        assert labels["program"] == "inc"
        assert metrics().counter("program_profiles_total").value(
            program="inc", outcome="ok") == 1
        names = [sp.name for sp in tracer().spans()]
        assert "profile.capture" in names

    def test_store_snapshot_is_json_ready(self):
        store = ProfileStore()
        f = jax.jit(lambda a: a + 1)
        capture_program_profile(f, (jnp.ones(8),), name="inc",
                                key=("k",), store=store)
        snap = store.snapshot()
        assert len(snap) == 1
        json.dumps(snap)
        assert snap[0]["name"] == "inc"
        assert snap[0]["flops"] is not None


# ---------------------------------------------------------------------------
# profile-on vs profile-off parity + per-key profiles
# ---------------------------------------------------------------------------


class TestProfiledFusedPrograms:
    @pytest.mark.parametrize("kind", ["ff", "rnn", "graph"])
    def test_profile_on_off_params_bitwise(self, kind, monkeypatch):
        make_net, make_data = MAKERS[kind]
        ds = make_data()

        monkeypatch.setenv("DL4J_PROFILE", "0")
        off = make_net()
        off.fit_epochs(ListDataSetIterator(ds, 12), 3)

        monkeypatch.setenv("DL4J_PROFILE", "1")
        on = make_net()
        on.fit_epochs(ListDataSetIterator(ds, 12), 3)

        assert _bitwise_equal(off.params, on.params)
        assert _bitwise_equal(off.updater_state, on.updater_state)

    def test_every_cached_key_has_a_profile(self, monkeypatch):
        monkeypatch.setenv("DL4J_PROFILE", "1")
        net = _ff_net()
        ds = _ff_data(48)
        net.fit_epochs(ListDataSetIterator(ds, 12), 2)
        net.fit_epochs(ListDataSetIterator(ds, 12), 2, telemetry=1)
        assert len(net._epoch_steps) == 2
        for key, program in net._epoch_steps.items():
            assert isinstance(program, ProfiledProgram)
            assert program.profiles, f"no profile captured for {key}"
            prof = program.profiles[0]
            assert prof.key == key
            assert prof.flops and prof.flops > 0
            assert prof.peak_bytes and prof.peak_bytes > 0
        # and they all landed in the process-global store
        assert len(profiles().find(name="MultiLayerNetwork")) == 2

    def test_profile_off_keeps_plain_path(self):
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(_ff_data(48), 12), 2)
        program = next(iter(net._epoch_steps.values()))
        assert isinstance(program, ProfiledProgram)
        assert program.profiles == []
        assert program._compiled == {}
        assert profiles().all() == []

    def test_wrapper_spmd_profile_parity(self, monkeypatch):
        from deeplearning4j_tpu.parallel import ParallelWrapper, build_mesh

        if len(jax.devices()) < 2:
            pytest.skip("needs the forced multi-device host platform")
        ds = _ff_data(64)

        def run():
            net = _ff_net()
            wrapper = ParallelWrapper(net, mesh=build_mesh())
            cache = wrapper.build_epoch_cache(ListDataSetIterator(ds, 16))
            assert cache is not None
            wrapper.fit_epochs(cache, 2)
            return net, wrapper

        monkeypatch.setenv("DL4J_PROFILE", "0")
        off, _ = run()
        monkeypatch.setenv("DL4J_PROFILE", "1")
        on, wrapper = run()
        assert _bitwise_equal(off.params, on.params)
        program = next(iter(wrapper._epoch_steps.values()))
        assert program.profiles
        assert program.profiles[0].flops > 0
        assert profiles().find(name="ParallelWrapper")

    def test_one_capture_per_signature(self, monkeypatch):
        """A second same-shaped run reuses the compiled executable; a
        new chunk length (new epoch_keys shape) captures exactly one
        more profile."""
        monkeypatch.setenv("DL4J_PROFILE", "1")
        net = _ff_net()
        ds = _ff_data(48)
        net.fit_epochs(ListDataSetIterator(ds, 12), 2)
        program = next(iter(net._epoch_steps.values()))
        assert len(program.profiles) == 1
        net.fit_epochs(ListDataSetIterator(ds, 12), 2)
        assert len(program.profiles) == 1  # same signature: no recapture
        net.fit_epochs(ListDataSetIterator(ds, 12), 3)
        assert len(program.profiles) == 2  # new chunk length

    def test_contracts_accept_profiled_programs(self):
        """The PR-7 program-contract checker keeps working against
        ProfiledProgram cache entries (lower/trace delegate)."""
        from deeplearning4j_tpu.analysis.contracts import (
            check_network_contracts)

        net = _ff_net()
        cache = net.build_epoch_cache(
            ListDataSetIterator(_ff_data(48), 12))
        net.fit_epochs(cache, 2)
        results = check_network_contracts(net, cache)
        assert all(v == [] for v in results.values())


# ---------------------------------------------------------------------------
# HBM watermarks + the budget-model runtime check
# ---------------------------------------------------------------------------


class TestHbmWatermarks:
    def test_sample_shape_and_gauges(self):
        x = jnp.ones((128, 128))  # keep one known live array
        sample = sample_hbm_watermark(tag="test")
        assert sample["tag"] == "test"
        assert sample["devices"]
        for entry in sample["devices"]:
            assert entry["source"] in ("memory_stats", "live_arrays")
            assert entry["bytes_in_use"] >= 0
        assert sample["max_bytes_in_use"] >= x.nbytes // len(
            jax.local_devices())
        snap = metrics().snapshot()
        assert "hbm_bytes_in_use" in snap
        assert any(sp.name == "hbm.watermark" for sp in tracer().spans())

    def test_live_array_accounting_sees_new_allocations(self):
        before = sum(live_array_bytes().values())
        big = jnp.ones((256, 1024), jnp.float32)
        after = sum(live_array_bytes().values())
        assert after - before >= big.nbytes

    def test_budget_model_matches_measured_cache_bytes(self):
        """The per-shard HBM budget model vs runtime allocation: the
        analytic resident bytes the build priced must match the bytes
        the device actually holds for the stacks."""
        cache = DeviceDataSetCache.build(
            ListDataSetIterator(_ff_data(96), 24))
        assert cache is not None
        check = validate_cache_budget(cache)
        assert check["within_tolerance"], check
        assert check["ratio"] == pytest.approx(1.0, abs=0.25)
        measured = cache_resident_bytes(cache)
        assert max(measured.values()) == check[
            "measured_per_device_bytes"]

    def test_watermarks_sampled_per_chunk_only_when_profiling(
            self, monkeypatch):
        ds = _ff_data(48)
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(ds, 12), 3, chunk_epochs=1)
        assert net._hbm_watermarks is None  # default off: never sampled

        monkeypatch.setenv("DL4J_PROFILE", "1")
        net2 = _ff_net()
        net2.fit_epochs(ListDataSetIterator(ds, 12), 3, chunk_epochs=1)
        assert len(net2._hbm_watermarks) == 3  # one per chunk boundary
        assert all(w["tag"] == "epoch.chunk"
                   for w in net2._hbm_watermarks)


# ---------------------------------------------------------------------------
# the cost model's step-time decomposition
# ---------------------------------------------------------------------------


class TestBoundedness:
    def test_compute_bound(self):
        out = classify_boundedness(
            flops=1e12, bytes_accessed=1e9, measured_s=0.02,
            peak_flops_per_s=1e14, peak_bytes_per_s=1e12)
        assert out["bound"] == "compute"
        assert out["optimal_s"] == pytest.approx(0.01)
        assert out["dispatch_wait_s"] == pytest.approx(0.01)
        assert out["dispatch_wait_pct"] == pytest.approx(50.0)
        assert out["arithmetic_intensity"] == pytest.approx(1000.0)

    def test_memory_bound(self):
        out = classify_boundedness(
            flops=1e9, bytes_accessed=1e10, measured_s=0.05,
            peak_flops_per_s=1e14, peak_bytes_per_s=1e11)
        assert out["bound"] == "memory"
        assert out["optimal_s"] == pytest.approx(0.1)
        assert out["dispatch_wait_s"] == 0.0  # measured below optimum

    def test_missing_inputs_degrade_to_none(self):
        out = classify_boundedness(None, None, None, 1e12, 1e11)
        assert out["bound"] is None
        assert out["optimal_s"] is None
        assert out["dispatch_wait_s"] is None

    def test_flops_divergence(self):
        assert flops_divergence_pct(100.0, 112.0) == pytest.approx(12.0)
        assert flops_divergence_pct(100.0, 95.0) == pytest.approx(-5.0)
        assert flops_divergence_pct(0.0, 95.0) is None
        assert flops_divergence_pct(100.0, None) is None


# ---------------------------------------------------------------------------
# profile-readback lint: chunk-boundary-only by contract
# ---------------------------------------------------------------------------


class TestProfileReadbackLint:
    def _lint(self, tmp_path, source):
        import textwrap

        path = tmp_path / "snippet.py"
        path.write_text(textwrap.dedent(source))
        config = LintConfig(root=str(tmp_path),
                            registered_markers={"chaos", "slow"})
        return run_lint(paths=[str(path)],
                        select=["host-sync-in-hot-path"], config=config)

    def test_profile_readback_in_hot_path_is_flagged(self, tmp_path):
        found = self._lint(tmp_path, """
            from deeplearning4j_tpu.monitor.memory import sample_hbm_watermark

            def _epoch_run_fn(self, xs):
                sample_hbm_watermark(tag="inside the program")
                return xs
            """)
        assert len(found) == 1
        assert "profile-readback" in found[0].message
        assert "chunk boundaries" in found[0].message

    def test_capture_in_traced_function_is_flagged(self, tmp_path):
        found = self._lint(tmp_path, """
            from deeplearning4j_tpu.analysis.annotations import traced
            from deeplearning4j_tpu.monitor.profile import capture_program_profile

            @traced
            def step(fn, args):
                return capture_program_profile(fn, args, name="x")
            """)
        assert len(found) == 1
        assert "profile-readback" in found[0].message

    def test_chunk_boundary_call_is_clean(self, tmp_path):
        found = self._lint(tmp_path, """
            from deeplearning4j_tpu.monitor.memory import sample_hbm_watermark

            def drive_chunks(net):
                # host-side, between dispatches: the permitted site
                return sample_hbm_watermark(tag="epoch.chunk")
            """)
        assert found == []

    def test_shipped_tree_is_lint_clean(self):
        """The new monitor/profile + monitor/memory path (and the chunk
        driver calling into it) introduces no findings."""
        config = LintConfig(root=REPO, registered_markers={"chaos",
                                                           "slow"})
        found = run_lint(
            paths=[os.path.join(REPO, "deeplearning4j_tpu", "monitor",
                                "profile.py"),
                   os.path.join(REPO, "deeplearning4j_tpu", "monitor",
                                "memory.py"),
                   os.path.join(REPO, "deeplearning4j_tpu", "perf",
                                "epoch_cache.py")],
            select=None, config=config)
        assert found == [], [f"{f.rule}: {f.message}" for f in found]


# ---------------------------------------------------------------------------
# bench error-path flush: profiles survive a wedge
# ---------------------------------------------------------------------------


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchProfileFlush:
    def test_error_path_flushes_collected_profiles(self):
        """The PR-6 partial-flush hardening extends to profile data: an
        error-path artifact still carries every ProgramProfile captured
        before the wedge, beside the telemetry block."""
        bench = _load_bench()
        f = jax.jit(lambda a: a * 3.0)
        capture_program_profile(f, (jnp.ones(16),), name="pre_wedge")
        extras = {"error": "backend unavailable: wedged device grant"}
        bench._refresh_telemetry(extras)
        assert extras["profile"]["programs"], "profiles lost on error path"
        assert extras["profile"]["programs"][0]["name"] == "pre_wedge"
        assert "spans" in extras["telemetry"]
        json.dumps(extras)  # artifact stays JSON-serializable

    def test_flops_entry_and_divergence_flag(self):
        bench = _load_bench()
        f = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((64, 64), jnp.float32)
        prof, _ = capture_program_profile(f, (a, a), name="gemm64")
        # per=1: whole-program counts; analytic = the textbook 2n^3
        entry = bench._flops_entry(2.0 * 64 ** 3, "2n^3", prof, 1)
        assert entry["cost_analysis_flops"] is not None
        assert abs(entry["flops_divergence_pct"]) < 10.0
        assert entry["flops_divergence_flag"] is False
        # an off-by-2x analytic formula trips the flag
        entry2 = bench._flops_entry(4.0 * 64 ** 3, "4n^3", prof, 1)
        assert entry2["flops_divergence_flag"] is True


class TestBenchFailuresAreLoud:
    """bench.py measures a TPU or fails: no CPU under a device metric's
    name, no default peaks, no failed section behind rc=0."""

    def test_unknown_device_kind_is_an_error(self):
        bench = _load_bench()
        with pytest.raises(ValueError, match="no peak"):
            bench._peaks_for("TPU v99 imaginary")
        assert bench._peaks_for("TPU v5 lite")["source"]

    def test_no_tpu_exits_nonzero_in_process(self):
        bench = _load_bench()
        with pytest.raises(SystemExit) as e:
            bench._device_stamp()
        assert e.value.code not in (0, None)

    def test_raising_section_makes_exit_code_nonzero(
            self, tmp_path, monkeypatch, capsys):
        bench = _load_bench()
        monkeypatch.chdir(tmp_path)  # bench_partial.json lands here
        monkeypatch.setenv("BENCH_ONLY", "gemm")
        monkeypatch.setenv("DL4J_PROFILE", "0")
        monkeypatch.setattr(bench, "_device_stamp", lambda: {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
        monkeypatch.setattr(bench, "_peaks",
                            lambda: bench.PEAKS["TPU v5 lite"])
        monkeypatch.setattr(
            bench, "bench_transformer",
            lambda on_progress=None: {"tokens_per_sec": 1.0})

        def boom():
            raise RuntimeError("section blew up")

        monkeypatch.setattr(bench, "bench_gemm", boom)
        assert bench.main() == 1
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["value"] == 1.0  # later sections still ran
        assert line["extras"]["gemm"]["error"] == "section blew up"
        assert line["extras"]["failed"] == ["gemm"]
        assert line["extras"]["device"]["kind"] == "TPU v5 lite"

        monkeypatch.setattr(bench, "bench_gemm", lambda: {"tflops": 1.0})
        assert bench.main() == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert "failed" not in line["extras"]


# ---------------------------------------------------------------------------
# bench_report.py: trajectory table + regression gate
# ---------------------------------------------------------------------------


def _write_round(tmp_path, n, value, *, metric="m_samples_per_sec",
                 rc=0, parsed=True, error=None, extras=None):
    payload = {"n": n, "rc": rc, "tail": ""}
    if parsed:
        ex = dict(extras or {})
        if error:
            ex["error"] = error
        payload["parsed"] = {"metric": metric, "value": value,
                             "unit": "x", "extras": ex}
    else:
        payload["parsed"] = None
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps(payload))
    return str(path)


class TestBenchReport:
    def test_improvement_exits_zero(self, tmp_path, capsys):
        files = [_write_round(tmp_path, 1, 100.0),
                 _write_round(tmp_path, 2, 130.0)]
        rc = bench_report.main(["--check"] + files)
        assert rc == 0
        out = capsys.readouterr().out
        assert "no regressions" in out

    def test_injected_regression_exits_nonzero(self, tmp_path, capsys):
        files = [_write_round(tmp_path, 1, 100.0),
                 _write_round(tmp_path, 2, 60.0)]
        rc = bench_report.main(["--check"] + files)
        assert rc == 1
        out = capsys.readouterr().out
        assert "REGRESSIONS" in out
        assert "40.0% below" in out

    def test_threshold_is_configurable(self, tmp_path):
        files = [_write_round(tmp_path, 1, 100.0),
                 _write_round(tmp_path, 2, 85.0)]
        assert bench_report.main(["--check"] + files) == 0  # 15% < 20%
        assert bench_report.main(["--check", "--threshold-pct", "10"]
                                 + files) == 1

    def test_wedge_round_is_flagged_and_skipped(self, tmp_path, capsys):
        """A wedge between two honest rounds is called out but neither
        scored as a regression nor used as a baseline."""
        files = [
            _write_round(tmp_path, 1, 100.0),
            _write_round(tmp_path, 2, None,
                         error="backend unavailable: backend init did "
                               "not complete in 90s (wedged device "
                               "grant?)"),
            _write_round(tmp_path, 3, 98.0),
        ]
        rc = bench_report.main(["--check"] + files)
        assert rc == 0  # 2% dip, wedge round contributes nothing
        out = capsys.readouterr().out
        assert "WEDGE" in out
        assert "excluded from regression scoring" in out

    def test_regression_detected_across_wedge_gap(self, tmp_path):
        """The baseline survives the wedge: r03 regressing against r01
        is caught even though r02 recorded only an error line."""
        files = [
            _write_round(tmp_path, 1, 100.0),
            _write_round(tmp_path, 2, None,
                         error="backend unavailable: wedged"),
            _write_round(tmp_path, 3, 50.0),
        ]
        assert bench_report.main(["--check"] + files) == 1

    def test_error_round_without_result_line(self, tmp_path, capsys):
        files = [_write_round(tmp_path, 1, 100.0),
                 _write_round(tmp_path, 2, None, rc=124, parsed=False)]
        assert bench_report.main(["--check"] + files) == 0
        assert "ERROR" in capsys.readouterr().out

    def test_headline_metric_change_is_not_a_trajectory(self, tmp_path):
        """r01's lenet headline vs r03's transformer headline are
        different experiments — never compared."""
        files = [
            _write_round(tmp_path, 1, 2_000_000.0, metric="lenet_sps"),
            _write_round(tmp_path, 2, 74_000.0, metric="tf_tokens"),
        ]
        assert bench_report.main(["--check"] + files) == 0

    def test_section_metrics_are_tracked(self, tmp_path):
        """A regression hiding in a section (headline steady) is still
        caught — the satellite metrics feed the gate too. The MFU series
        engages only for cost-analysis-sourced rounds (PR 14)."""
        cost = {"flops_source": {"cost_analysis_flops": 1.0e9}}
        files = [
            _write_round(tmp_path, 1, 100.0,
                         extras={"transformer_lm": {"mfu_pct": 8.0,
                                                    **cost}}),
            _write_round(tmp_path, 2, 101.0,
                         extras={"transformer_lm": {"mfu_pct": 2.0,
                                                    **cost}}),
        ]
        assert bench_report.main(["--check"] + files) == 1

    def test_analytic_mfu_rounds_never_enter_the_series(self, tmp_path,
                                                        capsys):
        """flops_source != cost_analysis ⇒ the round's MFU is not a
        trajectory point (an analytic number must never baseline or
        regress the compiled-FLOPs series) and the table flags it."""
        files = [
            _write_round(tmp_path, 1, 100.0,
                         extras={"transformer_lm": {
                             "mfu_pct": 8.0,
                             "flops_source": "analytic 6*N/token"}}),
            _write_round(tmp_path, 2, 101.0,
                         extras={"transformer_lm": {
                             "mfu_pct": 2.0,
                             "flops_source": {
                                 "cost_analysis_flops": None}}}),
        ]
        assert bench_report.main(["--check"] + files) == 0
        assert "[flops_source!=cost_analysis]" in capsys.readouterr().out

    def test_bf16_speedup_is_tracked(self, tmp_path):
        files = [
            _write_round(tmp_path, 1, 100.0,
                         extras={"transformer_lm": {
                             "train_step_bf16_speedup": 1.8}}),
            _write_round(tmp_path, 2, 101.0,
                         extras={"transformer_lm": {
                             "train_step_bf16_speedup": 1.0}}),
        ]
        assert bench_report.main(["--check"] + files) == 1

    def test_load_error_exit_code(self, tmp_path, capsys):
        missing = str(tmp_path / "BENCH_r99.json")
        assert bench_report.main([missing]) == 2
