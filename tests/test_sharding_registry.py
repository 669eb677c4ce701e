"""One mesh for everything: the DP×TP×PP sharding registry.

The contract under test (parallel/sharding_registry.py + the fused
epoch program routed through it + TP serving), on the conftest-forced
8-virtual-CPU-device mesh:

- spec lookup is TOTAL over FF/RNN/graph/TransformerLM param leaves —
  every leaf gets an explicit PartitionSpec, and an unmapped leaf
  raises ``UnmappedLeafError`` instead of silently replicating;
- a DP×TP mesh (2×4) runs ``fit_epochs`` as ONE donated GSPMD program
  per chunk (1 dispatch, counter-asserted) with final params <= 1e-6 of
  the single-device run for FF/RNN/graph across every step variant
  (plain / accum / guard / telemetry / mixed_bf16);
- elastic reshard generalizes to TOPOLOGY changes: 8×1 → 4×2 mid-run
  lands <= 1e-6 of the uninterrupted run (arXiv 2112.01075's
  redistribute, realized as snapshot-to-host + registry re-place);
- serving shards decode + the KV slot pool over ``model`` via the SAME
  registry specs: greedy streams token-identical to the unsharded
  server, per-shard pool budget green under ``validate_cache_budget``;
- ``check_network_contracts`` resolves its declared-axes set from the
  registry the placement stamped on the network, and flags a seeded
  sparse (cond-gated) collective over an undeclared axis.
"""

import contextlib
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.compile_cache import ensure_compile_cache
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.losses import LossFunction
from deeplearning4j_tpu.parallel import MeshSpec, build_mesh
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from deeplearning4j_tpu.parallel.sharding_registry import (
    ShardingRegistry,
    UnmappedLeafError,
    batch_spec,
    mesh_from_env,
    parse_mesh_shape,
)

TOL = dict(rtol=0, atol=1e-6)


def _ff_net(seed=0, policy=None):
    b = (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
         .updater(Updater.ADAM))
    if policy:
        b = b.dtype_policy(policy)
    conf = (b.list()
            .layer(0, L.DenseLayer(n_in=6, n_out=12, activation="tanh"))
            .layer(1, L.OutputLayer(n_in=12, n_out=3))
            .build())
    return MultiLayerNetwork(conf).init()


def _rnn_net(seed=0):
    conf = (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.02)
            .updater(Updater.SGD).list()
            .layer(0, L.GravesLSTM(n_in=3, n_out=6, activation="tanh"))
            .layer(1, L.RnnOutputLayer(n_in=6, n_out=4,
                                       loss_function=LossFunction.MCXENT))
            .build())
    return MultiLayerNetwork(conf).init()


def _ff_graph(seed=0):
    g = (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
         .updater(Updater.ADAM)
         .graph_builder()
         .add_inputs("in")
         .add_layer("dense", L.DenseLayer(n_in=6, n_out=12,
                                          activation="tanh"), "in")
         .add_layer("out", L.OutputLayer(n_in=12, n_out=3), "dense")
         .set_outputs("out"))
    return ComputationGraph(g.build()).init()


def _ff_data(n=96, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


def _rnn_data(n=48, t=5, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, t, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (n, t))]
    lm = (np.arange(t)[None, :]
          < rng.integers(3, t + 1, n)[:, None]).astype(np.float32)
    return DataSet(x, y, None, lm)


def _lm(seed=1, heads=4, kv_heads=None):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    lm = TransformerLM(vocab_size=50, d_model=16, num_heads=heads,
                      num_layers=2, d_ff=32, max_len=96,
                      pos_encoding="rope", seed=seed,
                      **({"num_kv_heads": kv_heads} if kv_heads else {}))
    lm._ensure_init()
    return lm


def _assert_params_close(a, b, **tol):
    fa = jax.tree_util.tree_leaves(jax.device_get(a))
    fb = jax.tree_util.tree_leaves(jax.device_get(b))
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), **tol)


DPTP = MeshSpec(data=2, model=4)


# ---------------------------------------------------------------------------
# spec totality
# ---------------------------------------------------------------------------
class TestSpecTotality:
    @pytest.mark.parametrize("factory", [_ff_net, _rnn_net, _ff_graph])
    def test_every_network_leaf_mapped(self, factory):
        net = factory()
        reg = ShardingRegistry.for_network(net, build_mesh(DPTP))
        specs = reg.leaf_specs(net.params)
        leaves = jax.tree_util.tree_leaves(net.params)
        assert len(specs) == len(leaves)
        assert all(isinstance(s, P) for s in specs)
        # TP actually shards something (the Megatron column/gate splits)
        assert any(s != P() for s in specs)

    def test_transformer_leaves_mapped(self):
        lm = _lm()
        reg = ShardingRegistry.for_transformer(lm, build_mesh(DPTP))
        specs = reg.leaf_specs(lm.params)
        assert len(specs) == len(jax.tree_util.tree_leaves(lm.params))
        assert reg.spec_for("blocks", 0, "attn", "wq") == P(None, MODEL_AXIS)
        assert reg.spec_for("blocks", 0, "attn", "wo") == P(MODEL_AXIS, None)

    def test_unmapped_leaf_raises(self):
        """A param leaf the spec tree does not cover must raise, not
        silently replicate."""
        net = _ff_net()
        reg = ShardingRegistry.for_network(net, build_mesh(DPTP))
        grown = jax.device_get(net.params)
        grown["0"]["mystery"] = np.zeros((3, 3), np.float32)
        with pytest.raises(UnmappedLeafError):
            reg.leaf_specs(grown)
        with pytest.raises(UnmappedLeafError):
            reg.spec_for("0", "mystery")

    def test_spec_for_subtree_is_not_a_leaf(self):
        net = _ff_net()
        reg = ShardingRegistry.for_network(net, build_mesh(DPTP))
        with pytest.raises(UnmappedLeafError):
            reg.spec_for("0")

    def test_pure_dp_mesh_replicates_all_explicitly(self):
        net = _ff_net()
        reg = ShardingRegistry.for_network(net, build_mesh())
        assert all(s == P() for s in reg.leaf_specs(net.params))
        assert reg.declared_axes == {DATA_AXIS}

    def test_declared_axes_tp(self):
        net = _ff_net()
        reg = ShardingRegistry.for_network(net, build_mesh(DPTP))
        assert reg.declared_axes == {DATA_AXIS, MODEL_AXIS}
        d = reg.describe()
        assert d["mesh"] == {"data": 2, "model": 4}
        assert d["sharded_leaves"] > 0


# ---------------------------------------------------------------------------
# env-driven mesh resolution
# ---------------------------------------------------------------------------
class TestMeshFromEnv:
    def test_parse_shapes(self):
        assert parse_mesh_shape("8x1") == MeshSpec(data=8, model=1, pipe=1)
        assert parse_mesh_shape("4x2") == MeshSpec(data=4, model=2, pipe=1)
        assert parse_mesh_shape("2x2x2") == MeshSpec(data=2, model=2,
                                                     pipe=2)
        with pytest.raises(ValueError):
            parse_mesh_shape("2x2x2x2")
        with pytest.raises(ValueError):
            parse_mesh_shape("axb")

    def test_mesh_shape_env(self, monkeypatch):
        monkeypatch.setenv("DL4J_MESH_SHAPE", "4x2")
        mesh = mesh_from_env()
        assert dict(mesh.shape) == {"data": 4, "model": 2}

    def test_tp_shards_env(self, monkeypatch):
        monkeypatch.delenv("DL4J_MESH_SHAPE", raising=False)
        monkeypatch.setenv("DL4J_TP_SHARDS", "4")
        mesh = mesh_from_env()
        assert dict(mesh.shape) == {"data": 2, "model": 4}

    def test_unset_is_none(self, monkeypatch):
        monkeypatch.delenv("DL4J_MESH_SHAPE", raising=False)
        monkeypatch.delenv("DL4J_TP_SHARDS", raising=False)
        assert mesh_from_env() is None

    def test_batch_spec_layouts(self):
        assert batch_spec(2) == P(DATA_AXIS, None)
        assert batch_spec(3, stacked=True) == P(None, DATA_AXIS, None)


# ---------------------------------------------------------------------------
# DP×TP fused epoch parity — one program, 1 dispatch/chunk, <=1e-6
# ---------------------------------------------------------------------------
def _fit_pair(factory, data_factory, batch, variant):
    kw = {}
    if variant == "accum":
        kw["accum_steps"] = 2
    kw["guard"] = "skip" if variant == "guard" else "off"
    if variant == "telemetry":
        kw["telemetry"] = True
    ref = factory(seed=5)
    it = ListDataSetIterator(data_factory(), batch)
    h0 = ref.fit_epochs(it, 3, **kw)
    tp = factory(seed=5)
    it = ListDataSetIterator(data_factory(), batch)
    tp._train_dispatches = 0
    h1 = tp.fit_epochs(it, 3, mesh=build_mesh(DPTP), **kw)
    return ref, tp, h0, h1


VARIANTS = ["plain", "accum", "guard", "telemetry"]


@contextlib.contextmanager
def _persistent_cache(directory):
    """JAX's persistent compilation cache off (``None``) or at
    ``directory`` for the block, whatever the process had: jax decides once
    a process whether it uses the cache, so it is reset on the way in and
    out. The package's own one-time placement runs first, or the first
    ``fit_epochs`` inside the block would move the directory back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    ensure_compile_cache()
    old = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_enable_compilation_cache", directory is not None)
    if directory is not None:
        jax.config.update("jax_compilation_cache_dir", directory)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old[0])
        jax.config.update("jax_compilation_cache_dir", old[1])
        cc.reset_cache()


def _reload_twice(directory):
    """The telemetry variant's programs compiled into the private
    ``directory``, every in-memory executable dropped, and the same body
    again: its programs come back from the directory (cache hits counted)
    and train to the same parameters and history, bit for bit."""
    from jax._src import monitoring

    hits = []
    monitoring.register_event_listener(
        lambda event, **kw: hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)
    with _persistent_cache(directory):
        _, first, _, h_first = _fit_pair(_rnn_net, _rnn_data, 8, "telemetry")
        programs = [f for f in os.listdir(directory)
                    if f.endswith("-cache")]
        cold = len(hits)
        jax.clear_caches()
        _, again, _, h_again = _fit_pair(_rnn_net, _rnn_data, 8, "telemetry")
    # every program of the second pass came from the directory
    assert len(hits) - cold >= len(programs) > 2, (len(hits), cold, programs)
    assert again._train_dispatches == 1
    _assert_params_close(first.params, again.params, rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(h_first), np.asarray(h_again))


class TestDpTpFusedParity:
    @pytest.fixture(autouse=True, scope="class")
    def _compiled_here(self):
        """These eight-device programs are compiled in the process that
        runs them: loaded from a persistent cache that an EARLIER process
        wrote, ``test_rnn[telemetry]``'s aborted the interpreter in about
        one run of three (ROADMAP D0; XLA:CPU, not this package)."""
        with _persistent_cache(None):
            yield

    def test_rnn_reloaded_from_a_cache_this_process_wrote(self, tmp_path):
        """The load-from-cache path, now that the class keeps out of the
        checkout's cache (``_reload_twice``), in a process of its own: there
        too XLA:CPU sometimes ends the interpreter (its eight devices wait at
        different collectives of the loaded program until ``rendezvous.cc``'s
        40 s termination timeout; 4 of 16 runs alone, most runs beside five
        busy workers), and that is recorded as an expected failure, not as a
        dead worker."""
        tests = os.path.dirname(os.path.abspath(__file__))
        run = subprocess.run(
            [sys.executable, "-c", "import conftest, test_sharding_registry "
             f"as t; t._reload_twice({str(tmp_path)!r})"],
            env=dict(os.environ, PYTHONPATH=os.pathsep.join(
                [os.path.dirname(tests), tests])),
            capture_output=True, text=True, timeout=600)
        if run.returncode == -signal.SIGABRT:
            pytest.xfail("XLA:CPU aborted running an eight-device program "
                         "loaded from the persistent cache (ROADMAP D0)")
        assert run.returncode == 0, run.stderr[-3000:]

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_ff(self, variant):
        ref, tp, h0, h1 = _fit_pair(_ff_net, _ff_data, 16, variant)
        assert tp._train_dispatches == 1  # ONE GSPMD program, all epochs
        _assert_params_close(ref.params, tp.params, **TOL)
        np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), **TOL)

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_rnn(self, variant):
        ref, tp, h0, h1 = _fit_pair(_rnn_net, _rnn_data, 8, variant)
        assert tp._train_dispatches == 1
        _assert_params_close(ref.params, tp.params, **TOL)
        np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), **TOL)

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_graph(self, variant):
        ref, tp, h0, h1 = _fit_pair(_ff_graph, _ff_data, 16, variant)
        assert tp._train_dispatches == 1
        _assert_params_close(ref.params, tp.params, **TOL)
        np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), **TOL)

    def test_ff_mixed_bf16(self):
        """mixed_bf16 parity: sharded vs single-device, both under the
        bf16-step/f32-masters policy (the PR-14 grouped-updater fallback
        handles TP-sharded state). Tolerance is bf16-scaled, not 1e-6:
        TP reorders the row-parallel GEMM's bf16 partial-sum reduction,
        and bf16's epsilon (~7.8e-3) bounds the achievable agreement —
        the f32 variants above hold the 1e-6 contract."""
        ref = _ff_net(seed=5, policy="mixed_bf16")
        ref.fit_epochs(ListDataSetIterator(_ff_data(), 16), 3)
        tp = _ff_net(seed=5, policy="mixed_bf16")
        tp._train_dispatches = 0
        tp.fit_epochs(ListDataSetIterator(_ff_data(), 16), 3,
                      mesh=build_mesh(DPTP))
        assert tp._train_dispatches == 1
        _assert_params_close(ref.params, tp.params, rtol=0, atol=8e-3)

    def test_tp_params_actually_sharded(self):
        """The fused run leaves the column-split Dense W sharded over
        ``model`` — proof the program ran TP, not replicated DP."""
        tp = _ff_net(seed=5)
        tp.fit_epochs(ListDataSetIterator(_ff_data(), 16), 2,
                      mesh=build_mesh(DPTP))
        w = tp.params["0"]["W"]  # P(None, "model"): 12/4 cols per shard
        shapes = {s.data.shape for s in w.addressable_shards}
        assert shapes == {(6, 3)}
        assert tp._sharding_registry.spec_for("0", "W") == P(None,
                                                             MODEL_AXIS)


# ---------------------------------------------------------------------------
# topology reshard: 8×1 → 4×2 mid-run
# ---------------------------------------------------------------------------
class TestTopologyReshard:
    def _run(self, factory, plan):
        net = factory(seed=9)
        seen = {"n": 0}

        def on_chunk(done):
            seen["n"] += 1
            if seen["n"] in plan:
                net.request_reshard(plan[seen["n"]])
            return False

        net.fit_epochs(ListDataSetIterator(_ff_data(), 16), 6,
                       chunk_epochs=2, mesh=build_mesh(MeshSpec(data=8)),
                       on_chunk=on_chunk)
        return net

    @pytest.mark.parametrize("factory", [_ff_net, _ff_graph])
    def test_8x1_to_4x2_mid_run(self, factory):
        """DP-only 8×1 for the first chunk, then a TOPOLOGY change to
        4×2 (DP shrinks, TP appears): final params <= 1e-6 of the
        uninterrupted 8×1 run — the registry re-derives every spec from
        the new mesh and the host snapshot lands on it."""
        base = self._run(factory, plan={})
        resharded = self._run(
            factory, plan={1: build_mesh(MeshSpec(data=4, model=2))})
        _assert_params_close(base.params, resharded.params, **TOL)
        # post-reshard placement really is the 4×2 registry layout
        reg = resharded._sharding_registry
        assert dict(reg.mesh.shape) == {"data": 4, "model": 2}
        assert reg.declared_axes == {DATA_AXIS, MODEL_AXIS}

    def test_4x2_back_to_8x1(self):
        base = self._run(_ff_net, plan={})
        there_and_back = self._run(_ff_net, plan={
            1: build_mesh(MeshSpec(data=4, model=2)),
            2: build_mesh(MeshSpec(data=8)),
        })
        _assert_params_close(base.params, there_and_back.params, **TOL)


# ---------------------------------------------------------------------------
# TP serving: same registry, token-identical streams, per-shard budget
# ---------------------------------------------------------------------------
class TestTpServing:
    def _streams(self, srv, prompts, n=12):
        reqs = [srv.submit(p, n) for p in prompts]
        srv.drain()
        return [list(r.tokens) for r in reqs]

    def test_greedy_token_identity_and_budget(self):
        from deeplearning4j_tpu.monitor.memory import validate_cache_budget
        from deeplearning4j_tpu.serving.server import DecodeServer

        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 50, size=k).astype(np.int32)
                   for k in (5, 9)]
        base = self._streams(
            DecodeServer(_lm(seed=3), slots=2, max_len=64), prompts)
        srv = DecodeServer(_lm(seed=3), slots=2, max_len=64,
                           mesh=build_mesh(DPTP))
        assert self._streams(srv, prompts) == base
        cache = srv.engine.cache
        assert cache.n_shard == 4  # Hkv=4 heads tile the model axis
        # pool physically sharded: each device holds Hkv/tp heads
        shapes = {s.data.shape for s in cache.k.addressable_shards}
        assert {sh[3] for sh in shapes} == {1}
        info = validate_cache_budget(cache)
        assert info["within_tolerance"], info
        assert info["n_shard"] == 4
        assert srv.stats()["kv_shards"] == 4

    def test_registry_specs_shared_with_training_side(self):
        """Serving consumes the SAME registry class/specs ``param_specs``
        declares — not a parallel sharding path."""
        from deeplearning4j_tpu.serving.server import DecodeServer

        srv = DecodeServer(_lm(seed=3), slots=2, max_len=64,
                           mesh=build_mesh(DPTP))
        reg = srv.engine.registry
        assert isinstance(reg, ShardingRegistry)
        assert reg.spec_for("blocks", 0, "attn", "wq") == P(None,
                                                            MODEL_AXIS)
        assert reg.kv_pool_spec(4) == P(None, None, None, MODEL_AXIS, None)

    def test_gqa_fallback_replicates_pool(self):
        """kv heads that do not tile the model axis fall back to a
        replicated pool (matching the wk/wv param fallback) — loudly,
        never an in-head split."""
        from deeplearning4j_tpu.serving.server import DecodeServer

        srv = DecodeServer(_lm(seed=3, heads=4, kv_heads=1), slots=2,
                           max_len=64, mesh=build_mesh(DPTP))
        cache = srv.engine.cache
        assert cache.n_shard == 1
        shapes = {s.data.shape for s in cache.k.addressable_shards}
        assert len(shapes) == 1  # full copy everywhere

    def test_env_mesh_reaches_server(self, monkeypatch):
        from deeplearning4j_tpu.serving.server import DecodeServer

        monkeypatch.setenv("DL4J_MESH_SHAPE", "2x4")
        srv = DecodeServer(_lm(seed=3), slots=2, max_len=64)
        assert srv.engine.registry is not None
        assert dict(srv.engine.mesh.shape) == {"data": 2, "model": 4}


# ---------------------------------------------------------------------------
# contracts: declared axes from the registry + seeded violation
# ---------------------------------------------------------------------------
class TestRegistryContracts:
    def test_tp_programs_green_under_registry_axes(self):
        from deeplearning4j_tpu.analysis.contracts import (
            check_network_contracts)

        net = _ff_net(seed=5)
        cache = net.build_epoch_cache(
            ListDataSetIterator(_ff_data(), 16), mesh=build_mesh(DPTP))
        net.fit_epochs(cache, 2)
        # declared-axes auto-resolved from net._sharding_registry
        results = check_network_contracts(net, cache, epochs=2)
        assert all(not v for v in results.values())

    def test_seeded_sparse_collective_over_undeclared_axis(self):
        """The hardest case: a collective that only fires on one branch
        of a ``cond`` (sparse/uneven), over an axis the registry never
        declared. The checker must walk into the branch sub-jaxpr and
        flag it."""
        from deeplearning4j_tpu.analysis.contracts import (
            check_network_contracts)
        from jax import shard_map

        net = _ff_net(seed=5)
        mesh = build_mesh(DPTP)
        cache = net.build_epoch_cache(
            ListDataSetIterator(_ff_data(), 16), mesh=mesh)
        net.fit_epochs(cache, 2)
        key = next(iter(net._epoch_steps))
        good = net._epoch_steps[key]

        def rogue(params, upd, nst, it, lr, xs, ys, fms, lms, keys):
            out = good(params, upd, nst, it, lr, xs, ys, fms, lms, keys)

            def body(x):
                return jax.lax.cond(
                    jnp.sum(x) > 0,
                    lambda v: jax.lax.psum(v, MODEL_AXIS),
                    lambda v: v, x)

            # check_vma=False: jax 0.9.0's varying-axes typing rejects
            # this deliberately sloppy cond before the checker sees it
            leak = shard_map(body, mesh=mesh,
                             in_specs=P(DATA_AXIS, MODEL_AXIS),
                             out_specs=P(DATA_AXIS, MODEL_AXIS),
                             check_vma=False)(
                                 jnp.ones((2, 4), jnp.float32))
            return out[:3] + (out[3] + jnp.sum(leak) * 0.0,) + out[4:]

        # registry that declares ONLY data (explicit replicate-all)
        from deeplearning4j_tpu.parallel.sharding_registry import (
            _replicate_all_tree)

        dp_only = ShardingRegistry(
            mesh, _replicate_all_tree(jax.device_get(net.params)),
            name="dp-only")
        net._epoch_steps = {key: rogue}
        results = check_network_contracts(
            net, cache, epochs=2, registry=dp_only,
            raise_on_violation=False, expect_donation=False)
        flat = "\n".join(v for vs in results.values() for v in vs)
        assert "undeclared mesh axis 'model'" in flat
