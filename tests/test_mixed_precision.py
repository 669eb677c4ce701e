"""Mixed-precision MFU push: the ``mixed_bf16`` master-weights policy.

The training mode the ISSUE-14 tentpole makes first-class: forward/
backward run on a bf16 parameter copy derived ONCE per step, gradients
upcast ONCE, and the updater applies to f32 master weights + f32 updater
state — the state the fused epoch program carries, donates, and
checkpoints. This suite pins the contracts:

- loss-curve parity ≤ 1e-2 vs float32 through the FUSED epoch pipeline
  (FF + graph) and the transformer train step;
- masters stay f32 (params + updater state) across fused training;
- telemetry-on/off stays BITWISE under the mixed policy, the NaN
  sentinel composes (a poisoned batch = exactly one skipped update),
  chunking is bitwise-invariant, accumulation composes;
- flash-vs-XLA attention parity at the fused-multi-step level under the
  mixed policy (interpret mode on CPU) — test_pallas.py covers the
  kernel, this covers the training-step wiring that flips per
  ``attn_impl`` / ``DL4J_ATTN_IMPL``;
- preempt → resume round-trips the masters BITWISE through the
  checkpoint (resume re-derives the bf16 copy in-program);
- the PR-7 contract checker passes over the mixed program (donation
  actually applied to masters + updater state);
- the fused updater apply is ONE flattened sweep: the optimizer tail's
  updater-math op count is depth-invariant (the PR-11 scan-body test's
  shape), and the grouped sweep is bitwise the per-layer reference.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes as dtypes_mod
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.models.transformer import TransformerLM
from deeplearning4j_tpu.nn.updater import (
    UpdaterSpec,
    apply_updater,
    grouped_apply_updaters,
    init_updater_state,
)
from deeplearning4j_tpu.parallel.cluster import FaultTolerantTrainer
from deeplearning4j_tpu.resilience import fail_nth, inject


def _ff_net(policy="mixed_bf16", seed=7, updater=Updater.ADAM):
    conf = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
        .updater(updater).dtype_policy(policy).list()
        .layer(0, L.DenseLayer(n_in=6, n_out=8, activation="tanh"))
        .layer(1, L.OutputLayer(n_in=8, n_out=3))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _graph_net(policy="mixed_bf16", seed=7):
    g = (
        NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
        .updater(Updater.ADAM).dtype_policy(policy)
        .graph_builder()
        .add_inputs("in")
        .add_layer("dense", L.DenseLayer(n_in=6, n_out=8,
                                         activation="tanh"), "in")
        .add_layer("out", L.OutputLayer(n_in=8, n_out=3), "dense")
        .set_outputs("out")
    )
    return ComputationGraph(g.build()).init()


def _ff_data(n=64, seed=0, poison_row=None):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    if poison_row is not None:
        x[poison_row] = np.nan
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


def _it(batch=16, **kw):
    return ListDataSetIterator(_ff_data(**kw), batch)


def _lm(policy="mixed_bf16", seed=1, attn="auto", depth=2, d=32, heads=4):
    return TransformerLM(vocab_size=61, d_model=d, num_heads=heads,
                        num_layers=depth, max_len=32, seed=seed,
                        dtype_policy=policy, attn_impl=attn).init()


def _toks(b=2, t=24, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 61, (b, t)), jnp.int32)


def _assert_bitwise(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# the policy itself
# ---------------------------------------------------------------------------


class TestPolicy:
    def test_mixed_bf16_resolves_to_master_weights(self):
        p = dtypes_mod.policy_from_name("mixed_bf16")
        assert p.master_weights
        assert p.param_dtype == jnp.float32
        assert p.compute_dtype == jnp.bfloat16
        # the legacy per-use-cast policy is untouched
        for name in ("bf16", "mixed_bfloat16"):
            assert not dtypes_mod.policy_from_name(name).master_weights

    def test_compute_copy_and_master_grads(self):
        p = dtypes_mod.MIXED_BF16_MASTER
        tree = {"W": jnp.ones((3, 2), jnp.float32)}
        copy = p.compute_copy(tree)
        assert copy["W"].dtype == jnp.bfloat16
        up = p.master_grads({"W": jnp.ones((3, 2), jnp.bfloat16)})
        assert up["W"].dtype == jnp.float32
        # identity under the single-dtype policies
        assert dtypes_mod.FLOAT32.compute_copy(tree) is tree
        assert dtypes_mod.FLOAT32.master_grads(tree) is tree

    def test_grad_zeros_carry_param_dtype(self):
        p = dtypes_mod.MIXED_BF16_MASTER
        z = p.grad_zeros({"W": jnp.ones((2, 2), jnp.bfloat16)})
        assert z["W"].dtype == jnp.float32 and z["W"].shape == (2, 2)


# ---------------------------------------------------------------------------
# fused-epoch training under the mixed policy
# ---------------------------------------------------------------------------


class TestFusedEpochMixed:
    def test_ff_loss_curve_parity_vs_f32(self):
        h32 = _ff_net("float32").fit_epochs(_it(), 3)
        net = _ff_net("mixed_bf16")
        hmx = net.fit_epochs(_it(), 3)
        assert hmx is not None and hmx.shape == (3, 4)
        assert np.abs(np.asarray(h32) - np.asarray(hmx)).max() <= 1e-2
        # masters + updater state stay f32 across fused training
        for leaf in jax.tree_util.tree_leaves(net.params):
            assert leaf.dtype == jnp.float32
        for leaf in jax.tree_util.tree_leaves(net.updater_state):
            assert leaf.dtype == jnp.float32

    def test_graph_loss_curve_parity_vs_f32(self):
        h32 = _graph_net("float32").fit_epochs(_it(), 3)
        net = _graph_net("mixed_bf16")
        hmx = net.fit_epochs(_it(), 3)
        assert hmx is not None
        assert np.abs(np.asarray(h32) - np.asarray(hmx)).max() <= 1e-2
        for leaf in jax.tree_util.tree_leaves(net.params):
            assert leaf.dtype == jnp.float32

    def test_fused_vs_per_step_bitwise(self):
        """The test_epoch_cache bitwise contract holds under the mixed
        policy: fit_epochs vs the per-step train program driven on the
        fused path's exact RNG stream — same bf16 copies, same f32
        master updates, bit for bit."""
        from deeplearning4j_tpu.perf.epoch_cache import (
            DeviceDataSetCache, epoch_schedule)

        fused, ref = _ff_net(), _ff_net()
        cache = DeviceDataSetCache.build(_it())
        hist = fused.fit_epochs(cache, 3)
        keys = jax.random.split(ref._rng, 4)
        ref._rng = keys[0]
        it = 0
        ref_hist = []
        for ekey in keys[1:]:
            order, skeys = epoch_schedule(ekey, cache.n_batches, True)
            row = []
            for j in range(cache.n_batches):
                i = int(np.asarray(order)[j])
                (ref.params, ref.updater_state, ref.net_state, loss,
                 *_) = ref._train_step(
                    ref.params, ref.updater_state, ref.net_state,
                    jnp.asarray(it, jnp.int32), jnp.asarray(1.0),
                    (cache.features[i], cache.labels[i], None,
                     cache.labels_mask[i]), skeys[j])
                it += 1
                row.append(np.asarray(loss))
            ref_hist.append(row)
        np.testing.assert_array_equal(np.asarray(hist),
                                      np.asarray(ref_hist))
        _assert_bitwise(fused.params, ref.params)
        _assert_bitwise(fused.updater_state, ref.updater_state)

    def test_telemetry_on_off_bitwise(self):
        a = _ff_net()
        a.fit_epochs(_it(), 3, telemetry=False)
        b = _ff_net()
        b.fit_epochs(_it(), 3, telemetry=True)
        assert b._last_metrics is not None
        assert b._last_metrics.shape == (3, 4, 4)
        # the pack's norms are f32 over the upcast grads
        assert b._last_metrics.dtype == jnp.float32
        assert bool(jnp.all(jnp.isfinite(b._last_metrics)))
        _assert_bitwise(a.params, b.params)

    def test_guard_composes_one_poisoned_batch_one_skip(self):
        net = _ff_net()
        hist = net.fit_epochs(_it(poison_row=20), 2, shuffle=False,
                              guard="skip")
        assert hist is not None
        trips = np.asarray(net._last_sentinel)
        assert trips.shape == (2, 4)
        # the poisoned batch trips once per epoch; every other update
        # applies and the masters stay finite
        assert trips.sum(axis=1).tolist() == [1, 1]
        for leaf in jax.tree_util.tree_leaves(net.params):
            assert bool(jnp.all(jnp.isfinite(leaf)))

    def test_accumulation_composes(self):
        a = _ff_net()
        ha = a.fit_epochs(_it(), 2, shuffle=False, accum_steps=1)
        b = _ff_net()
        hb = b.fit_epochs(_it(), 2, shuffle=False, accum_steps=2)
        # bf16 microbatch grads upcast into an f32 sum: equal to the
        # unaccumulated bf16 step up to bf16 rounding of the per-micro
        # grads, well inside the policy's parity budget
        assert np.abs(np.asarray(ha) - np.asarray(hb)).max() <= 1e-2

    def test_contract_checker_green_over_mixed_program(self):
        from deeplearning4j_tpu.analysis.contracts import (
            check_network_contracts)

        net = _ff_net()
        cache = net.build_epoch_cache(_it())
        net.fit_epochs(cache, 2, telemetry=True)
        # raises ContractViolation on any failure: donation must be
        # applied to every master/updater/net-state leaf of the lowered
        # mixed program, no host callbacks, outputs match the key
        results = check_network_contracts(net, cache)
        assert results and all(not v for v in results.values())


# ---------------------------------------------------------------------------
# transformer: mixed masters + the flash training path
# ---------------------------------------------------------------------------


class TestTransformerMixed:
    def test_master_state_layout_and_parity_vs_f32(self):
        tok = _toks()
        lmf = _lm("float32")
        lmm = _lm("mixed_bf16")
        assert lmm.params["embed"].dtype == jnp.float32
        assert lmm.opt_state["embed"]["m"].dtype == jnp.float32
        diffs = []
        for _ in range(5):
            la = lmf.fit_batch(tok)
            lb = lmm.fit_batch(tok)
            diffs.append(abs(la - lb))
        assert max(diffs) <= 1e-2
        # masters still f32 after donated steps
        assert lmm.params["embed"].dtype == jnp.float32

    def test_fused_multi_step_flash_vs_xla_under_mixed(self):
        """The fused-training-program-level flash/XLA equivalence the
        kernel tests cannot see: K optimizer steps as ONE program per
        attention impl (interpret-mode Pallas on CPU), same losses and
        same trained masters to bf16 tolerance."""
        tok = _toks(t=16)
        lms = {}
        for impl in ("xla", "flash"):
            lm = _lm("mixed_bf16", attn=impl)
            multi = lm.make_multi_train_step(3)
            loss = lm.fit_batch_multi(tok, multi_step=multi, k=3)
            lms[impl] = (lm, loss)
        assert abs(lms["xla"][1] - lms["flash"][1]) <= 2e-2
        for a, b in zip(jax.tree_util.tree_leaves(lms["xla"][0].params),
                        jax.tree_util.tree_leaves(lms["flash"][0].params)):
            assert np.abs(np.asarray(a) - np.asarray(b)).max() <= 1e-2

    def test_attn_env_override(self, monkeypatch):
        lm = _lm()
        monkeypatch.setenv("DL4J_ATTN_IMPL", "flash")
        assert lm._attn_impl(16, train=True) == "flash"
        assert lm._attn_impl(16) == "flash"
        monkeypatch.setenv("DL4J_ATTN_IMPL", "xla")
        assert lm._attn_impl(4096, train=True) == "xla"
        monkeypatch.setenv("DL4J_ATTN_IMPL", "bogus")
        with pytest.raises(ValueError):
            lm._attn_impl(16)

    def test_auto_training_default_flips_flash_when_head_dim_tiles(
            self, monkeypatch):
        import deeplearning4j_tpu.models.transformer as tf_mod

        # pretend a real TPU backend is attached
        monkeypatch.setattr(tf_mod, "flash_default_interpret",
                            lambda: False)
        big = TransformerLM(vocab_size=61, d_model=512, num_heads=8,
                            max_len=1024, num_layers=1)
        assert big._head_dim_tiles()
        # training: flash regardless of sequence length
        assert big._attn_impl(1024, train=True) == "flash"
        # inference keeps the measured t>=4k crossover
        assert big._attn_impl(1024) == "xla"
        assert big._attn_impl(4096) == "flash"
        small = TransformerLM(vocab_size=61, d_model=32, num_heads=4,
                              max_len=1024, num_layers=1)
        assert not small._head_dim_tiles()
        assert small._attn_impl(1024, train=True) == "xla"

    def test_interpret_backend_stays_on_xla(self):
        # CPU (interpret-mode Pallas) never auto-selects flash
        lm = _lm()
        assert lm._attn_impl(1024, train=True) == "xla"


# ---------------------------------------------------------------------------
# the fused (grouped) updater apply
# ---------------------------------------------------------------------------


def _adam_mln(depth, seed=3):
    b = (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.01)
         .updater(Updater.ADAM).list())
    for i in range(depth):
        b = b.layer(i, L.DenseLayer(n_in=8, n_out=8, activation="tanh"))
    b = b.layer(depth, L.OutputLayer(n_in=8, n_out=4))
    return MultiLayerNetwork(b.build()).init()


UPDATER_MATH_PRIMS = {"sqrt", "rsqrt", "integer_pow", "pow", "div"}


def _updater_tail_math_eqns(net):
    grads = jax.tree_util.tree_map(jnp.ones_like, net.params)
    jaxpr = jax.make_jaxpr(
        lambda p, u, g: net._apply_updaters(
            p, u, g, jnp.asarray(0, jnp.int32), jnp.asarray(1.0)))(
        net.params, net.updater_state, grads)
    names = []
    stack = [jaxpr.jaxpr]
    while stack:
        j = stack.pop()
        for e in j.eqns:
            names.append(e.primitive.name)
            for v in e.params.values():
                if hasattr(v, "jaxpr"):
                    stack.append(v.jaxpr)
    return sum(1 for n in names if n in UPDATER_MATH_PRIMS)


class TestFusedUpdaterSweep:
    def test_optimizer_tail_math_is_depth_invariant(self):
        """The PR-11 scan-body assertion shape, on the optimizer tail:
        the traced Adam math (sqrt/pow/div chains) is per GROUP, not
        per layer — its op count must not move with depth. (The per-leaf
        residue is only reshape/slice data movement.)"""
        shallow = _updater_tail_math_eqns(_adam_mln(2))
        deep = _updater_tail_math_eqns(_adam_mln(8))
        assert shallow == deep, (shallow, deep)

    @pytest.mark.parametrize("kind", [Updater.SGD, Updater.NESTEROVS,
                                      Updater.ADAGRAD, Updater.RMSPROP,
                                      Updater.ADADELTA, Updater.ADAM])
    def test_grouped_matches_per_layer_reference(self, kind):
        """Bitwise against the pre-PR-14 per-layer loop: elementwise
        updater ops on a concatenation ARE the per-leaf ops."""
        rng = np.random.default_rng(abs(hash(str(kind))) % 1000)
        specs = [UpdaterSpec(kind=kind, learning_rate=0.05),
                 UpdaterSpec(kind=kind, learning_rate=0.05)]
        params, state, grads = {}, {}, {}
        for i in range(2):
            p = {"W": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32),
                 "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32)}
            params[str(i)] = p
            state[str(i)] = init_updater_state(specs[i], p)
            grads[str(i)] = jax.tree_util.tree_map(
                lambda a: jnp.asarray(
                    rng.normal(size=a.shape), jnp.float32), p)
        scale = jnp.asarray(1.0)
        step_count = jnp.asarray(2)
        new_p, new_u = grouped_apply_updaters(
            [(str(i), specs[i]) for i in range(2)], params, state,
            grads, scale, step_count)
        # reference: the per-layer loop this PR replaced
        ref_p, ref_u = {}, {}
        for i, spec in enumerate(specs):
            si = str(i)
            steps_i, upd_i = apply_updater(
                spec, grads[si], state[si], scale, step_count)
            ref_p[si] = jax.tree_util.tree_map(
                lambda p, s: p - s.astype(p.dtype), params[si], steps_i)
            ref_u[si] = upd_i
        _assert_bitwise(new_p, ref_p)
        _assert_bitwise(new_u, ref_u)
        assert (jax.tree_util.tree_structure(new_p)
                == jax.tree_util.tree_structure(ref_p))

    def test_tp_sharded_state_takes_the_per_layer_fallback(self):
        """A concat over leaves with MIXED shardings makes the
        partitioner replicate every leaf (exact on jax 0.9.0, but each
        chip gathers the whole parameter set) — the flat sweep must
        refuse tensor-parallel placements and take the per-layer apply.
        End-to-end: a TP-sharded per-step fit matches the unsharded
        reference (the pre-PR-14 test_parallel contract)."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.nn.updater import flat_apply_safe
        from deeplearning4j_tpu.parallel import MeshSpec, build_mesh
        from deeplearning4j_tpu.parallel.tensor_parallel import (
            shard_network_params)

        ref, tp = _ff_net("float32"), _ff_net("float32")
        assert flat_apply_safe(ref.params)
        mesh = build_mesh(MeshSpec(data=2, model=4))
        shard_network_params(tp, mesh)
        assert not flat_apply_safe(tp.params)
        rng = np.random.default_rng(3)
        ds = DataSet(rng.normal(size=(16, 6)).astype(np.float32),
                     np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)])
        ref.fit(ds)
        with mesh:
            tp.fit(ds)
        np.testing.assert_allclose(ref.get_flat_params(),
                                   tp.get_flat_params(),
                                   rtol=2e-4, atol=1e-5)

    def test_bias_lr_and_per_layer_normalization_preserved(self):
        from deeplearning4j_tpu.nn.conf.enums import GradientNormalization

        rng = np.random.default_rng(5)
        specs = [
            UpdaterSpec(kind=Updater.SGD, learning_rate=0.1,
                        bias_learning_rate=0.01),
            UpdaterSpec(
                kind=Updater.SGD, learning_rate=0.1,
                gradient_normalization=(
                    GradientNormalization.CLIP_L2_PER_LAYER),
                gradient_normalization_threshold=0.5),
        ]
        params, state, grads = {}, {}, {}
        for i, spec in enumerate(specs):
            p = {"W": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32),
                 "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32)}
            params[str(i)] = p
            state[str(i)] = init_updater_state(spec, p)
            grads[str(i)] = jax.tree_util.tree_map(
                lambda a: jnp.asarray(
                    rng.normal(size=a.shape) * 3.0, jnp.float32), p)
        new_p, _ = grouped_apply_updaters(
            [(str(i), specs[i]) for i in range(2)], params, state,
            grads, jnp.asarray(1.0), jnp.asarray(1))
        # layer 0: bias stepped with its own lr
        np.testing.assert_allclose(
            np.asarray(new_p["0"]["b"]),
            np.asarray(params["0"]["b"] - 0.01 * grads["0"]["b"]),
            rtol=0, atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(new_p["0"]["W"]),
            np.asarray(params["0"]["W"] - 0.1 * grads["0"]["W"]),
            rtol=0, atol=1e-7)
        # layer 1: clipped with the LAYER's own norm (not the group's)
        from deeplearning4j_tpu.nn.updater import normalize_gradients

        g1 = normalize_gradients(specs[1], grads["1"])
        np.testing.assert_allclose(
            np.asarray(new_p["1"]["W"]),
            np.asarray(params["1"]["W"] - 0.1 * g1["W"]),
            rtol=0, atol=1e-7)

    @pytest.mark.parametrize("n", [15, 1024, 1025, 2 * 1024 + 10])
    def test_flat_vectors_are_whole_tiles(self, n):
        """Every flat vector of a group is a multiple of 1,024 elements
        long (the TPU's dense one-dimensional tile; ResNet-18's
        11,176,970 became ``[1117697, 10]`` under an (8, 128) tile), by a
        zeros tail INSIDE the one concatenate, and a group that already
        is gets no tail."""
        spec = UpdaterSpec(kind=Updater.ADAM, learning_rate=0.05)
        sweep = _sweep([("0", spec)])
        params, state, grads = _layers_of({"W": (n - 3, 1), "b": (3,)}, spec)
        cats = _concat_eqns(sweep, params, state, grads)
        assert len(cats) == 3                       # g, m, v
        for e in cats:
            assert e.outvars[0].aval.shape[0] % 1024 == 0
            assert e.outvars[0].aval.shape[0] - n < 1024
            assert len(e.invars) == (2 if n % 1024 == 0 else 3)
        new_p, new_u = sweep(params, state, grads)
        assert new_p["0"]["W"].shape == (n - 3, 1)
        assert new_u["0"]["b"]["m"].shape == (3,)

    @pytest.mark.parametrize("kind", [Updater.SGD, Updater.NESTEROVS,
                                      Updater.ADAGRAD, Updater.RMSPROP,
                                      Updater.ADADELTA, Updater.ADAM])
    def test_zero_tail_is_finite_and_dropped(self, kind):
        """The tail is g = 0 over zero state: its step is
        0 / (sqrt(0) + eps) = 0 for every kind, never a NaN or an
        infinity (each op checked as it runs), and the live leaves are
        bitwise the per-layer loop's — on a first step and on a second
        one over the state the first left."""
        from deeplearning4j_tpu.nn.updater import per_layer_apply_updaters

        spec = UpdaterSpec(kind=kind, learning_rate=0.05)
        items = [("0", spec), ("1", spec)]
        params, state, grads = _layers_of({"W": (5, 3), "b": (3,)}, spec,
                                          keys=("0", "1"), seed=11)
        with jax.debug_nans(True), jax.debug_infs(True):
            for step in (1, 2):
                new_p, new_u = grouped_apply_updaters(
                    items, params, state, grads, jnp.asarray(1.0),
                    jnp.asarray(step))
                ref_p, ref_u = per_layer_apply_updaters(
                    items, params, state, grads, jnp.asarray(1.0),
                    jnp.asarray(step))
                _assert_bitwise(new_p, ref_p)
                _assert_bitwise(new_u, ref_u)
                assert (jax.tree_util.tree_structure(new_u)
                        == jax.tree_util.tree_structure(ref_u))
                params, state = new_p, new_u

    @pytest.mark.parametrize("case", ["one_leaf", "bias_lr"])
    def test_tail_keeps_the_groups_structure(self, case):
        """A group of one leaf stays that leaf (no concatenate, no
        tail); ``bias_learning_rate`` splits the leaves into two groups
        and each is padded on its own."""
        from deeplearning4j_tpu.nn.updater import per_layer_apply_updaters

        if case == "one_leaf":
            spec = UpdaterSpec(kind=Updater.ADAM, learning_rate=0.05)
            items = [("0", spec)]
            params, state, grads = _layers_of({"W": (7, 3)}, spec)
            want = []
        else:
            spec = UpdaterSpec(kind=Updater.ADAM, learning_rate=0.05,
                               bias_learning_rate=0.01)
            items = [("0", spec), ("1", spec)]
            params, state, grads = _layers_of({"W": (600, 1), "b": (3,)},
                                              spec, keys=("0", "1"))
            # g, m, v of the weights (1,200 -> 2,048), then of the biases
            want = [2048] * 3 + [1024] * 3
        cats = _concat_eqns(_sweep(items), params, state, grads)
        assert [e.outvars[0].aval.shape[0] for e in cats] == want
        assert all(len(e.invars) == 3 for e in cats)
        new = _sweep(items)(params, state, grads)
        ref = per_layer_apply_updaters(items, params, state, grads,
                                       jnp.asarray(1.0), jnp.asarray(1))
        _assert_bitwise(new, ref)
        assert (jax.tree_util.tree_structure(new)
                == jax.tree_util.tree_structure(ref))


def _layers_of(shapes, spec, keys=("0",), seed=0):
    """``(params, state, grads)`` of layers ``keys``, each with float32
    leaves of ``shapes``."""
    rng = np.random.default_rng(seed)
    draw = lambda: {key: {k: jnp.asarray(rng.normal(size=s), jnp.float32)
                          for k, s in shapes.items()} for key in keys}
    params = draw()
    state = {key: init_updater_state(spec, params[key]) for key in keys}
    return params, state, draw()


def _sweep(items):
    return lambda p, u, g: grouped_apply_updaters(
        items, p, u, g, jnp.asarray(1.0), jnp.asarray(1))


def _concat_eqns(fn, *args):
    return [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "concatenate"]


# ---------------------------------------------------------------------------
# preempt -> resume: masters round-trip through the checkpoint
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestPreemptResumeMixed:
    def test_masters_round_trip_bitwise(self, tmp_path):
        """Preempt a mixed_bf16 fused run at a chunk boundary, resume in
        a fresh process-equivalent, finish: bitwise the uninterrupted
        run. The checkpoint stores the f32 MASTERS (params/updater state
        are never bf16 at rest); resume re-derives the bf16 copy
        in-program on the first step."""
        base = _ff_net()
        base.fit_epochs(_it(), 4, chunk_epochs=1)

        n2 = _ff_net()
        t2 = FaultTolerantTrainer(n2, str(tmp_path))
        with inject("preempt.chunk", fail_nth(2)):
            t2.fit_epochs(_it(), 4, chunk_epochs=1)
        assert t2.preempted and n2._epoch_cursor == 2

        n3 = _ff_net()
        t3 = FaultTolerantTrainer(n3, str(tmp_path))
        assert t3.resume()
        # the restored state is the f32 masters
        for leaf in jax.tree_util.tree_leaves(n3.params):
            assert leaf.dtype == jnp.float32
        t3.fit_epochs(_it(), 4, chunk_epochs=1)
        _assert_bitwise(base.params, n3.params)
        _assert_bitwise(base.updater_state, n3.updater_state)
        assert base.iteration_count == n3.iteration_count
