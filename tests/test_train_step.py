"""The seam of ``nn/train_step.py``: ONE optimizer step for both network
classes and ``ParallelWrapper``.

The contract: ``fit_epochs`` on either class, under every program key, is
bitwise a per-step loop that calls ``optimizer_step`` on the same key
stream; the step's result has one fixed order; and the host LR scale is
applied by every path of both classes (the graph's unguarded step and
the wrapper's graph replay ignored it before the step was written once).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn import train_step
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.train_step import optimizer_step
from deeplearning4j_tpu.perf.epoch_cache import epoch_schedule


def _builder(updater):
    return (NeuralNetConfiguration.Builder().seed(0).learning_rate(0.05)
            .updater(updater))


def _mln(updater=Updater.ADAM):
    conf = (_builder(updater).list()
            .layer(0, L.DenseLayer(n_in=6, n_out=12, activation="tanh"))
            .layer(1, L.OutputLayer(n_in=12, n_out=3))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph(updater=Updater.ADAM):
    g = (_builder(updater).graph_builder().add_inputs("in")
         .add_layer("dense", L.DenseLayer(n_in=6, n_out=12,
                                          activation="tanh"), "in")
         .add_layer("out", L.OutputLayer(n_in=12, n_out=3), "dense")
         .set_outputs("out"))
    return ComputationGraph(g.build()).init()


NETS = {"MultiLayerNetwork": _mln, "ComputationGraph": _graph}


def _data(n=96, poison_rows=None):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    if poison_rows is not None:
        x[poison_rows] = np.nan
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


def _assert_bitwise(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# (accum_steps, guard, metrics_stride): the _epoch_steps key's three
# trace-time arguments
VARIANTS = {
    "plain": (1, False, 0),
    "accum2": (2, False, 0),
    "guard": (1, True, 0),
    "telemetry": (1, False, 1),
    "guard+telemetry": (1, True, 1),
    "accum2+guard+telemetry": (2, True, 1),
}


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("cls", list(NETS))
def test_fit_epochs_is_the_per_step_loop_over_optimizer_step(cls, variant):
    """fit_epochs == a host loop of optimizer_step on the fused run's key
    stream, bitwise, for every program key on both classes — and the step
    returns (params, updater, net_state, loss, rnn, tripped, metrics) in
    that order whatever is compiled in. Batch 1 is poisoned, so a guarded
    variant really skips a step."""
    accum, guard, stride = VARIANTS[variant]
    epochs, batch = 2, 24
    data = ListDataSetIterator(_data(poison_rows=slice(24, 48)), batch)
    fused, ref = NETS[cls](), NETS[cls]()
    cache = fused.build_epoch_cache(data, accum_steps=accum)
    hist = fused.fit_epochs(cache, epochs, accum_steps=accum,
                            guard="skip" if guard else "off",
                            telemetry=stride)

    step = jax.jit(functools.partial(
        optimizer_step, ref, accum_steps=accum, guard=guard,
        metrics_stride=stride))
    keys = jax.random.split(ref._rng, epochs + 1)
    losses, trips, mets = [], [], []
    it = 0
    for ekey in keys[1:]:
        order, skeys = epoch_schedule(ekey, cache.n_batches, True)
        for j in range(cache.n_batches):
            one = jax.tree_util.tree_map(
                lambda a: a[int(order[j])], cache.stacks)
            out = step(ref.params, ref.updater_state, ref.net_state,
                       jnp.asarray(it, jnp.int32),
                       jnp.asarray(1.0, jnp.float32), one, skeys[j])
            assert len(out) == 7
            ref.params, ref.updater_state, ref.net_state, loss, rnn, \
                tripped, m = out
            assert rnn is None  # feed-forward net, no carry asked for
            assert (tripped is not None) == guard
            assert (m is not None) == bool(stride)
            losses.append(loss), trips.append(tripped), mets.append(m)
            it += 1

    n = cache.n_batches
    np.testing.assert_array_equal(
        np.asarray(hist), np.asarray(losses).reshape(epochs, n))
    _assert_bitwise(fused.params, ref.params)
    _assert_bitwise(fused.updater_state, ref.updater_state)
    _assert_bitwise(fused.net_state, ref.net_state)
    if guard:
        want = np.asarray(trips).reshape(epochs, n)
        assert want.sum() == epochs  # the poisoned batch, once an epoch
        np.testing.assert_array_equal(
            np.asarray(fused._last_sentinel), want)
    if stride:
        np.testing.assert_array_equal(
            np.asarray(fused._last_metrics),
            np.asarray(mets).reshape(epochs, n, -1))


def _delta(net, scale, fit):
    net._lr_scale_host = scale
    before = jax.tree_util.tree_map(np.asarray, net.params)
    fit(net)
    return jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - b, net.params, before)


@pytest.mark.parametrize("path", ["fit_epochs", "fit", "fit_steps"])
@pytest.mark.parametrize("cls", list(NETS))
def test_host_lr_scale_applies_unguarded(cls, path):
    """A halved host LR scale halves an SGD update on BOTH classes, with
    the sentinel off and on the per-step paths: before the step was
    written once, a ComputationGraph ignored ``_lr_scale_host`` wherever
    the step was not guarded."""
    ds = _data(24)
    fit = {
        "fit_epochs": lambda net: net.fit_epochs(
            ListDataSetIterator(ds, 24), 1, guard="off"),
        "fit": lambda net: net.fit(ds),
        "fit_steps": lambda net: net.fit_steps(ds, 1),
    }[path]
    full = _delta(NETS[cls](Updater.SGD), 1.0, fit)
    half = _delta(NETS[cls](Updater.SGD), 0.5, fit)
    for f, h in zip(jax.tree_util.tree_leaves(full),
                    jax.tree_util.tree_leaves(half)):
        assert np.abs(f).max() > 0
        np.testing.assert_allclose(h, 0.5 * f, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("cls", list(NETS))
def test_wrapper_replay_applies_host_lr_scale(cls, monkeypatch):
    """The per-step replay ``ParallelWrapper.fit_epochs`` hands the chunk
    driver (DL4J_NAN_GUARD=raise localization) takes the step the fused,
    guarded run took — host LR scale included; the wrapper's graph
    replay used to drop it."""
    from deeplearning4j_tpu.parallel import ParallelWrapper, build_mesh

    seen = {}

    def spy(net, cache, *args, replay_step=None, **kw):
        seen.update(replay=replay_step, cache=cache)

    monkeypatch.setattr(train_step, "drive_epoch_chunks", spy)
    net = NETS[cls](Updater.SGD)
    wrapper = ParallelWrapper(net, mesh=build_mesh())
    wrapper.fit_epochs(ListDataSetIterator(_data(48), 24), 1,
                       guard="raise")

    def replayed(scale):
        net._lr_scale_host = scale
        state = jax.tree_util.tree_map(
            jnp.copy, (net.params, net.updater_state, net.net_state))
        p, _, _, loss = seen["replay"](*state, 0, 1, jax.random.PRNGKey(3))
        assert np.isfinite(float(loss))
        return jax.tree_util.tree_map(
            lambda a, b: np.asarray(a) - np.asarray(b), p, net.params)

    for f, h in zip(jax.tree_util.tree_leaves(replayed(1.0)),
                    jax.tree_util.tree_leaves(replayed(0.5))):
        assert np.abs(f).max() > 0
        np.testing.assert_allclose(h, 0.5 * f, rtol=1e-4, atol=1e-7)


def test_tbptt_scan_windows_a_graph_and_a_network_alike():
    """``tbptt_fn`` is one function for both classes: the same recurrent
    net as a MultiLayerNetwork and as a one-input graph, fed the same
    sequence with 2D-per-window masks, takes the same fused TBPTT steps
    (same seeds, same layer order => same params)."""
    from deeplearning4j_tpu.nn.conf.enums import BackpropType

    def lstm():
        return L.GravesLSTM(n_in=3, n_out=5, activation="tanh")

    def out():
        return L.RnnOutputLayer(n_in=5, n_out=4)

    b = (_builder(Updater.SGD).list().layer(0, lstm()).layer(1, out())
         .backprop_type(BackpropType.TRUNCATED_BPTT)
         .t_bptt_forward_length(4).t_bptt_backward_length(4))
    mln = MultiLayerNetwork(b.build()).init()
    g = (_builder(Updater.SGD).graph_builder().add_inputs("in")
         .add_layer("0", lstm(), "in").add_layer("1", out(), "0")
         .set_outputs("1").backprop_type(BackpropType.TRUNCATED_BPTT)
         .t_bptt_forward_length(4).t_bptt_backward_length(4))
    graph = ComputationGraph(g.build()).init()
    graph.params = jax.tree_util.tree_map(jnp.copy, mln.params)

    rng = np.random.default_rng(1)
    x = rng.normal(size=(6, 12, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (6, 12))]
    lm = (np.arange(12)[None, :] < rng.integers(5, 13, 6)[:, None]
          ).astype(np.float32)
    ds = DataSet(x, y, None, lm)
    mln.fit(ds)
    graph.fit(ds)
    assert mln.iteration_count == graph.iteration_count == 3
    assert mln._train_dispatches == graph._train_dispatches == 0  # fused
    for a, c in zip(jax.tree_util.tree_leaves(mln.params),
                    jax.tree_util.tree_leaves(graph.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-6, atol=1e-7)
