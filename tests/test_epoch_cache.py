"""Epoch-pipeline tests: HBM dataset cache + whole-epoch scan fusion.

The contract under test (perf/epoch_cache.py + fit_epochs on both network
classes): the fused E-epochs x N-batches program must be OBSERVATIONALLY
identical to the per-step train loop fed the identical RNG key stream —
bitwise, not approximately — while making one train-program dispatch per
chunk instead of one per batch; over-budget datasets must silently take the
streaming path with identical results; and the fused program must compile
once per (bucket shape, chunk length), never once per call.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import (
    AsyncDataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.losses import LossFunction
from deeplearning4j_tpu.perf.epoch_cache import (
    DeviceDataSetCache,
    DeviceMultiDataSetCache,
    epoch_schedule,
)


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


def _ff_data(n=100, seed=0, label_mask=False):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    lm = (rng.integers(0, 2, n).astype(np.float32)
          if label_mask else None)
    return DataSet(x, y, None, lm)


def _rnn_data(n=24, t=7, seed=0, label_mask=False):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, t, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (n, t))]
    lm = None
    if label_mask:
        # variable-length sequences: mask out tails
        lm = (np.arange(t)[None, :]
              < rng.integers(3, t + 1, n)[:, None]).astype(np.float32)
    return DataSet(x, y, None, lm)


def _reference_epochs(net, cache, epochs, shuffle=True):
    """The per-step train program (the exact jitted step ``fit`` uses)
    driven host-side on the fused path's RNG stream: chunk keys split off
    ``net._rng`` the same way, each epoch key expanded through
    ``epoch_schedule`` eagerly. This IS the per-step fit loop on identical
    keys — the comparison the bitwise suite is named for. One body for
    both network classes: the batch is ``cache.stacks`` indexed."""
    keys = jax.random.split(net._rng, epochs + 1)
    net._rng = keys[0]
    it = net.iteration_count
    history = []
    for ekey in keys[1:]:
        order, skeys = epoch_schedule(ekey, cache.n_batches, shuffle)
        order = np.asarray(order)
        row = []
        for j in range(cache.n_batches):
            batch = jax.tree_util.tree_map(lambda a: a[int(order[j])],
                                           cache.stacks)
            (net.params, net.updater_state, net.net_state, loss,
             *_) = net._train_step(
                net.params, net.updater_state, net.net_state,
                jnp.asarray(it, jnp.int32),
                jnp.asarray(net._lr_scale_host, jnp.float32),
                batch, skeys[j])
            it += 1
            row.append(np.asarray(loss))
        history.append(row)
    net.iteration_count = it
    return np.asarray(history)




class TestDeviceDataSetCache:
    def test_stacks_pads_and_counts(self):
        # 100 @ batch 32 → 32/32/32/4, one uniform bucket of 32
        cache = DeviceDataSetCache.build(
            ListDataSetIterator(_ff_data(100), batch_size=32))
        assert cache is not None
        assert cache.n_batches == 4
        assert cache.batch == 32
        assert cache.total_examples == 100
        assert cache.features.shape == (4, 32, 6)
        assert cache.labels.shape == (4, 32, 3)
        # pad rows of the 4-row tail are masked out; real rows masked in
        lm = np.asarray(cache.labels_mask)
        assert lm.shape == (4, 32)
        np.testing.assert_array_equal(lm[3, :4], 1.0)
        np.testing.assert_array_equal(lm[3, 4:], 0.0)
        np.testing.assert_array_equal(lm[0], 1.0)

    def test_ragged_batches_share_max_bucket(self):
        # 70 @ batch 48 → 48/22 → buckets 64/32 → one uniform 64 stack
        cache = DeviceDataSetCache.build(
            ListDataSetIterator(_ff_data(70), batch_size=48))
        assert cache.batch == 64
        assert cache.features.shape == (2, 64, 6)

    def test_over_budget_returns_none_and_resets_iterator(self):
        it = ListDataSetIterator(_ff_data(4096, seed=1), batch_size=512)
        assert DeviceDataSetCache.build(it, budget_mb=0.01) is None
        # the iterator is handed back ready for the streaming path
        assert len(list(it)) == 8

    def test_env_budget_zero_disables(self, monkeypatch):
        monkeypatch.setenv("DL4J_DEVICE_CACHE_MB", "0")
        assert DeviceDataSetCache.build(
            ListDataSetIterator(_ff_data(), batch_size=32)) is None

    def test_unstackable_shapes_return_none(self):
        rng = np.random.default_rng(0)
        batches = [DataSet(rng.normal(size=(8, 6)).astype(np.float32),
                           np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]),
                   DataSet(rng.normal(size=(8, 5)).astype(np.float32),
                           np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)])]
        assert DeviceDataSetCache.build(batches) is None

    def test_missing_labels_return_none(self):
        assert DeviceDataSetCache.build(
            [DataSet(np.zeros((8, 6), np.float32))]) is None

    def test_multi_cache_promotes_datasets(self):
        cache = DeviceMultiDataSetCache.build(
            ListDataSetIterator(_ff_data(100), batch_size=32))
        assert cache is not None
        assert cache.n_batches == 4
        assert cache.features[0].shape == (4, 32, 6)
        assert cache.labels_masks[0].shape == (4, 32)


class TestBitwiseEquivalenceMLN:
    """fit_epochs vs the per-step train loop on identical RNG key streams
    — bitwise (rtol=0, atol=0), FF and RNN, with and without label masks."""

    @pytest.mark.parametrize("label_mask", [False, True])
    def test_ff(self, label_mask):
        data = _ff_data(100, label_mask=label_mask)
        fused, ref = _ff_net(), _ff_net()
        cache = DeviceDataSetCache.build(
            ListDataSetIterator(data, batch_size=32))
        hist = fused.fit_epochs(cache, 3)
        ref_hist = _reference_epochs(ref, cache, 3)
        np.testing.assert_array_equal(np.asarray(hist), ref_hist)
        np.testing.assert_array_equal(fused.get_flat_params(),
                                      ref.get_flat_params())
        assert fused.iteration_count == ref.iteration_count == 12

    @pytest.mark.parametrize("label_mask", [False, True])
    def test_rnn(self, label_mask):
        data = _rnn_data(15, t=5, label_mask=label_mask)
        fused, ref = _rnn_net(), _rnn_net()
        cache = DeviceDataSetCache.build(
            ListDataSetIterator(data, batch_size=6))  # 6/6/3 → bucket 8
        assert cache.batch == 8
        hist = fused.fit_epochs(cache, 2)
        ref_hist = _reference_epochs(ref, cache, 2)
        np.testing.assert_array_equal(np.asarray(hist), ref_hist)
        np.testing.assert_array_equal(fused.get_flat_params(),
                                      ref.get_flat_params())

    def test_no_shuffle_preserves_batch_order(self):
        data = _ff_data(96)
        fused, ref = _ff_net(), _ff_net()
        cache = DeviceDataSetCache.build(
            ListDataSetIterator(data, batch_size=32))
        hist = fused.fit_epochs(cache, 2, shuffle=False)
        ref_hist = _reference_epochs(ref, cache, 2, shuffle=False)
        np.testing.assert_array_equal(np.asarray(hist), ref_hist)
        np.testing.assert_array_equal(fused.get_flat_params(),
                                      ref.get_flat_params())


class TestBitwiseEquivalenceGraph:
    @pytest.mark.parametrize("label_mask", [False, True])
    def test_ff_graph(self, label_mask):
        data = _ff_data(100, label_mask=label_mask)
        fused, ref = _ff_graph(), _ff_graph()
        fused.init(), ref.init()
        cache = DeviceMultiDataSetCache.build(
            ListDataSetIterator(data, batch_size=32))
        hist = fused.fit_epochs(cache, 3)
        ref_hist = _reference_epochs(ref, cache, 3)
        np.testing.assert_array_equal(np.asarray(hist), ref_hist)
        for k, v in ref.get_param_table().items():
            np.testing.assert_array_equal(fused.get_param_table()[k], v)
        assert fused.iteration_count == ref.iteration_count == 12


class TestDispatchAndChunking:
    def test_one_dispatch_per_run_without_listeners(self):
        net = _ff_net()
        hist = net.fit_epochs(ListDataSetIterator(_ff_data(), 32), 5)
        assert net._train_dispatches == 1  # E epochs x N batches, one launch
        assert hist.shape == (5, 4)
        assert net.iteration_count == 20

    def test_listeners_get_per_epoch_decision_points(self):
        from deeplearning4j_tpu.optimize.listeners import (
            CollectScoresIterationListener)

        net = _ff_net()
        lst = CollectScoresIterationListener()
        net.set_listeners(lst)
        hist = net.fit_epochs(ListDataSetIterator(_ff_data(), 32), 3)
        # default chunk with listeners = 1 epoch → one chunk_done per
        # epoch; the listener reconstructs EVERY step's (iteration,
        # loss) from the chunk history (PR-6 fused listener protocol)
        assert [it for it, _ in lst.scores] == list(range(1, 13))
        np.testing.assert_allclose(
            [s for _, s in lst.scores], np.asarray(hist).reshape(-1),
            rtol=1e-6)
        assert net._train_dispatches == 3

    def test_explicit_chunking_concatenates_history(self):
        net = _ff_net()
        hist = net.fit_epochs(ListDataSetIterator(_ff_data(96), 32), 4,
                              chunk_epochs=2)
        assert hist.shape == (4, 3)
        assert net._train_dispatches == 2

    def test_recompile_guard_one_miss_per_bucket_shape(self):
        """One jit cache miss per (bucket shape, chunk length) — a second
        run over the same-shaped cache must NOT recompile; a new bucket
        shape must add exactly one entry."""
        net = _ff_net()
        net.fit_epochs(ListDataSetIterator(_ff_data(100, seed=0), 32), 2)
        step = net._epoch_steps[(True, 1, True, 0)]
        assert step._cache_size() == 1
        net.fit_epochs(ListDataSetIterator(_ff_data(100, seed=7), 32), 2)
        assert step._cache_size() == 1  # same shapes: no new compile
        net.fit_epochs(ListDataSetIterator(_ff_data(200, seed=7), 64), 2)
        assert step._cache_size() == 2  # new bucket (64): exactly one more


class TestBudgetFallback:
    def test_oversized_dataset_streams_with_identical_results(self):
        """The HBM-budget fallback is silent and exact: a dataset over
        DL4J_DEVICE_CACHE_MB takes the async streaming path and produces
        the same parameters as the plain per-step fit loop."""
        data = _ff_data(128, seed=3)
        a, b = _ff_net(), _ff_net()
        hist = a.fit_epochs(ListDataSetIterator(data, 32), 2,
                            cache_mb=1e-4)  # ~100 KB dataset over budget
        assert hist is None  # fallback ran — no fused history
        for _ in range(2):
            b.fit(ListDataSetIterator(data, 32))
        np.testing.assert_array_equal(a.get_flat_params(),
                                      b.get_flat_params())
        assert a.iteration_count == b.iteration_count == 8

    def test_graph_fallback_matches_plain_fit(self):
        data = _ff_data(64, seed=4)
        a, b = _ff_graph().init(), _ff_graph().init()
        hist = a.fit_epochs(ListDataSetIterator(data, 32), 2, cache_mb=1e-4)
        assert hist is None
        for _ in range(2):
            b.fit(ListDataSetIterator(data, 32))
        for k, v in b.get_param_table().items():
            np.testing.assert_array_equal(a.get_param_table()[k], v)

    def test_tbptt_config_falls_back_to_fit(self):
        from deeplearning4j_tpu.nn.conf.enums import BackpropType

        conf = (
            NeuralNetConfiguration.Builder().seed(0).learning_rate(0.02)
            .updater(Updater.SGD).list()
            .backprop_type(BackpropType.TRUNCATED_BPTT)
            .t_bptt_forward_length(4).t_bptt_backward_length(4)
            .layer(0, L.GravesLSTM(n_in=3, n_out=6, activation="tanh"))
            .layer(1, L.RnnOutputLayer(n_in=6, n_out=4,
                                       loss_function=LossFunction.MCXENT))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        hist = net.fit_epochs(ListDataSetIterator(_rnn_data(16, t=8), 8), 2)
        assert hist is None
        assert np.isfinite(net.score_value)
        assert net.iteration_count > 0

    def test_cache_plus_fallback_config_raises(self):
        from deeplearning4j_tpu.nn.conf.enums import OptimizationAlgorithm

        conf = (
            NeuralNetConfiguration.Builder().seed(0).learning_rate(0.1)
            .optimization_algo(OptimizationAlgorithm.LBFGS).list()
            .layer(0, L.DenseLayer(n_in=6, n_out=8, activation="tanh"))
            .layer(1, L.OutputLayer(n_in=8, n_out=3))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        cache = DeviceDataSetCache.build(
            ListDataSetIterator(_ff_data(), 32))
        with pytest.raises(ValueError, match="per-step fit loop"):
            net.fit_epochs(cache, 2)


class TestEarlyStoppingFused:
    def _config(self, data, **kw):
        from deeplearning4j_tpu.earlystopping import (
            DataSetLossCalculator, EarlyStoppingConfiguration,
            MaxEpochsTerminationCondition)

        builder = (EarlyStoppingConfiguration.Builder()
                   .epoch_termination_conditions(
                       MaxEpochsTerminationCondition(kw.get("max_epochs", 3)))
                   .score_calculator(
                       DataSetLossCalculator(ListDataSetIterator(data, 32))))
        if kw.get("iter_conditions"):
            builder.iteration_termination_conditions(*kw["iter_conditions"])
        return builder.build()

    def test_fused_trainer_one_dispatch_per_epoch(self):
        from deeplearning4j_tpu.earlystopping import EarlyStoppingTrainer

        data = _ff_data(100, seed=5)
        net = _ff_net()
        trainer = EarlyStoppingTrainer(
            self._config(data), net, ListDataSetIterator(data, 32),
            fuse_epochs=True)
        result = trainer.fit()
        assert result.total_epochs == 3
        assert result.best_model is not None
        assert np.isfinite(result.best_model_score)
        # the cache was built once; each epoch was ONE fused dispatch
        assert net._train_dispatches == 3

    def test_fused_trainer_iteration_condition_sees_every_batch(self):
        from deeplearning4j_tpu.earlystopping import (
            EarlyStoppingResult, EarlyStoppingTrainer,
            MaxScoreIterationTerminationCondition)

        data = _ff_data(100, seed=5)
        trainer = EarlyStoppingTrainer(
            self._config(data, iter_conditions=[
                MaxScoreIterationTerminationCondition(1e-9)]),
            _ff_net(), ListDataSetIterator(data, 32), fuse_epochs=True)
        result = trainer.fit()
        # per-batch losses from the [1, N] history trip the condition
        assert (result.termination_reason
                is EarlyStoppingResult.TerminationReason.ITERATION_TERMINATION)
        assert result.total_epochs == 1


class TestAsyncIteratorLifecycle:
    def _batches(self, n=10):
        return ListDataSetIterator(_ff_data(n * 8, seed=9), batch_size=8)

    def test_reset_midepoch_joins_producer(self):
        it = AsyncDataSetIterator(self._batches(), queue_size=2)
        assert it.has_next()
        it.next()  # mid-epoch
        thread = it._thread
        it.reset()
        assert thread is not None and not thread.is_alive()
        assert it._thread is None
        # and the restarted generation yields the full epoch
        assert len(list(it)) == 10

    def test_repeated_midepoch_resets_do_not_accumulate_threads(self):
        it = AsyncDataSetIterator(self._batches(), queue_size=2)
        baseline = threading.active_count()
        for _ in range(5):
            assert it.has_next()
            it.next()
            it.reset()
        deadline = time.time() + 5
        while threading.active_count() > baseline and time.time() < deadline:
            time.sleep(0.01)
        assert threading.active_count() <= baseline

    def test_straggler_generation_cannot_pollute_new_queue(self):
        class Slow(ListDataSetIterator):
            def next(self, num=None):
                time.sleep(0.02)
                return super().next(num)

        ds = _ff_data(40, seed=9)
        it = AsyncDataSetIterator(Slow(ds, batch_size=8), queue_size=2)
        assert it.has_next()
        it.reset()  # old producer may still be mid-next()
        batches = list(it)
        # exactly one epoch: no stale batch from the previous generation
        assert len(batches) == 5
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(b.features) for b in batches]),
            np.asarray(ds.features))

    def test_queue_size_governs_device_buffer_depth(self):
        class Counting(ListDataSetIterator):
            produced = 0

            def next(self, num=None):
                type(self).produced += 1
                return super().next(num)

        Counting.produced = 0
        it = AsyncDataSetIterator(
            Counting(_ff_data(80, seed=9), batch_size=8), queue_size=3)
        assert it.has_next()  # starts producer, peeks one batch
        deadline = time.time() + 5
        # producer runs ahead: queue(3) + peeked(1) + one in-flight put
        while Counting.produced < 4 and time.time() < deadline:
            time.sleep(0.01)
        assert 4 <= Counting.produced <= 5
        time.sleep(0.1)  # no further production while consumer idles
        assert Counting.produced <= 5
        it.reset()
