"""Data-parallel training: synchronous all-reduce DP + parameter averaging.

Replaces the reference's two DP mechanisms (SURVEY §2.5):
1. Spark parameter averaging / gradient averaging
   (SparkDl4jMultiLayer.fitDataSet, spark/dl4j-spark/.../SparkDl4jMultiLayer
   .java:338-445) — broadcast params, independent local fits per partition,
   accumulator-sum + divide, aggregate updater state.
2. The Akka iterative-reduce parameter server (MasterActor.java:61,
   IterativeReduceWorkRouter.java:48-53).

``ParallelWrapper`` is the idiomatic TPU replacement: ONE SPMD program —
batch sharded over the mesh's ``data`` axis, params replicated; XLA GSPMD
inserts the gradient all-reduce over ICI. Mathematically identical to
training with the global batch on one device, with none of the reference's
host-side averaging machinery.

``ParameterAveragingTrainer`` keeps the reference's exact semantics
(independent replicas, periodic averaging — local SGD) for parity testing
and for DCN-separated multi-slice topologies where per-step all-reduce is
too expensive: replicas live on a leading axis sharded over ``data``; the
local step is ``jax.vmap``-ed; averaging is a mean over the replica axis
(XLA lowers it to an all-reduce when sharded). Updater state is averaged
with the params, matching the reference's UpdaterAggregator.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import dtypes as dtypes_mod
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.train_step import (
    jit_step, run_fused_epochs, step_state)
from deeplearning4j_tpu.nn.updater import apply_updater, lr_policy_scale

logger = logging.getLogger(__name__)
from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, MeshSpec, build_mesh)


class ParallelWrapper:
    """Synchronous data-parallel fit over a mesh (the ParallelWrapper role
    named in the reference's roadmap; here it is a thin pjit wrapper).

    Usage::

        wrapper = ParallelWrapper(net, mesh=build_mesh())
        wrapper.fit(iterator)        # global batch must divide mesh 'data' size
    """

    def __init__(self, network, mesh: Optional[Mesh] = None,
                 donate: bool = True, fsdp: bool = False):
        """``fsdp=True`` shards parameters AND updater state over the
        ``data`` axis (ZeRO-3, parallel/fsdp.py) instead of replicating —
        per-device state drops ~N×; GSPMD all-gathers weights on use and
        reduce-scatters gradients. Batch sizes must then divide the data
        axis (no ragged-tail fallback: it would need a gather/reshard
        round-trip per tail)."""
        self.network = network
        self.mesh = mesh or build_mesh()
        self._donate = donate
        self.fsdp = fsdp
        self._epoch_steps = {}  # fused SPMD epoch program per (shuffle, K, guard, stride)
        network._ensure_init()
        self._place_params()

    @property
    def data_parallelism(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    def _place_params(self):
        """Registry-driven placement: the sharding registry derives every
        leaf's spec from the mesh (replicated on pure-DP meshes, Megatron
        TP where the mesh has a ``model`` axis), composed with FSDP over
        ``data`` via ``with_fsdp`` when ``fsdp=True``. The derived
        param/updater shardings are kept for the epoch program's
        out_shardings pin."""
        from deeplearning4j_tpu.parallel.sharding_registry import (
            ShardingRegistry)

        net = self.network
        reg = ShardingRegistry.for_network(net, self.mesh)
        if self.fsdp:
            reg = reg.with_fsdp(net.params)
        self._registry = reg
        self._param_shardings = reg.param_shardings(net.params)
        self._upd_shardings = reg.state_shardings(net.updater_state)
        reg.place_network(net)

    def request_reshard(self, mesh) -> None:
        """Request a mid-run elastic reshard of an in-flight
        ``fit_epochs`` run (``None`` = back to one device). Forwards to
        the wrapped network — the chunk driver reads the pending-mesh
        latch off the network — and the wrapper's own reshard callback
        re-pins its per-mesh programs at the next chunk boundary."""
        self.network.request_reshard(mesh)

    def _apply_reshard(self, mesh, cache) -> None:
        """The chunk driver's reshard actuator for the wrapper path:
        snapshot the trainable state to host, swap the wrapper onto the
        new mesh, drop every per-mesh artifact (epoch programs with
        pinned out_shardings, the FSDP re-jitted step, FSDP sharding
        specs), re-place state, and re-place the dataset cache. Values
        are untouched — only placement changes."""
        net = self.network
        net.params, net.updater_state, net.net_state = jax.device_get(
            (net.params, net.updater_state, net.net_state))
        self.mesh = mesh if mesh is not None else build_mesh(
            MeshSpec(data=1), devices=jax.devices()[:1])
        self._epoch_steps.clear()
        self.__dict__.pop("_fsdp_train_step", None)
        self._place_params()
        cache.respec(self.mesh)

    @functools.cached_property
    def _fsdp_train_step(self):  # dl4j-lint: disable=adhoc-out-shardings -- shardings sourced from the registry (with_fsdp); only the jit pin lives here
        """The network's step re-jitted with out_shardings pinned to the
        registry's FSDP specs (``optimizer_step``'s result order: params
        and updater state first) so donated updates keep state sharded
        across steps."""
        return jit_step(
            self.network,
            donate_argnums=(0, 1, 2) if self._donate else (),
            out_shardings=(self._param_shardings, self._upd_shardings)
            + (None,) * 5)

    def _shard_batch(self, arr):
        from deeplearning4j_tpu.parallel.sharding_registry import (
            batch_sharding)

        if arr is None:
            return None
        return jax.device_put(
            jnp.asarray(arr), batch_sharding(self.mesh, np.ndim(arr)))

    def fit(self, data, num_epochs: int = 1):
        """fit(DataSetIterator | DataSet). Batches are sharded over 'data';
        the jitted step is the network's own — GSPMD handles the rest.

        TBPTT and non-SGD-solver configurations are NOT sharded: they
        delegate wholly to the network's own fit (windowed/solver
        semantics preserved, single device) rather than silently taking
        different steps on the mesh."""
        net = self.network
        if not self._shardable():
            if self.fsdp:
                # the network's own fit path has no pinned out_shardings:
                # one step would silently re-replicate the state and lose
                # the N-fold memory saving fsdp=True was chosen for
                raise ValueError(
                    "ParallelWrapper(fsdp=True) does not support "
                    "TBPTT/non-SGD/pretrain/SCORE-lr/iterations>1 "
                    "configs; use fsdp=False (replicated DP) for these")
            from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

            reason = ("non-shardable config (TBPTT/non-SGD/pretrain/"
                      "SCORE-lr/iterations>1)"
                      if isinstance(net, MultiLayerNetwork)
                      else f"{type(net).__name__} does not speak the "
                           "MLN sharded-step protocol")
            logger.info("ParallelWrapper: %s — delegating to the "
                        "network's own fit path (single device)", reason)
            net.fit(data, num_epochs=num_epochs)
            return self
        if isinstance(data, DataSet):
            self._fit_one(data)
            return self
        for _ in range(num_epochs):
            if hasattr(data, "reset"):
                data.reset()
            for ds in data:
                self._fit_one(ds)
        return self

    def _shardable(self) -> bool:
        """Configs whose per-batch semantics the sharded one-step path
        preserves exactly — the same exclusion list as
        MultiLayerNetwork.fit_steps (multilayer.py). Only
        MultiLayerNetwork has the per-batch host protocol around the
        shared step (_sgd_step/_post_iteration, DataSet batches); every
        other model (e.g. ComputationGraph off the CLI) delegates to its
        own fit path rather than crashing mid-mesh-setup."""
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        if not isinstance(self.network, MultiLayerNetwork):
            return False
        from deeplearning4j_tpu.nn.conf.enums import (
            BackpropType, LearningRatePolicy, OptimizationAlgorithm)

        conf = self.network.conf
        gc = conf.global_conf
        return (gc.optimization_algo
                == OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT
                and conf.backprop_type != BackpropType.TRUNCATED_BPTT
                and not getattr(conf, "pretrain", False)
                and gc.lr_policy != LearningRatePolicy.SCORE
                and max(1, gc.iterations) == 1)

    def _fit_one(self, ds: DataSet):
        net = self.network
        dp = self.data_parallelism
        if ds.num_examples() % dp:
            if self.fsdp:
                raise ValueError(
                    f"FSDP requires batch sizes divisible by the data "
                    f"axis (got {ds.num_examples()} vs dp={dp}); pad or "
                    f"drop the tail batch")
            # ragged tail batch (e.g. last CSV batch): ONE unsharded
            # optimizer step — same per-batch step count as the sharded
            # path (net.fit would run gc.iterations steps and over-weight
            # the smallest batch); params are replicated, so it is exact
            logger.debug(
                "batch of %d not divisible by dp=%d; running unsharded",
                ds.num_examples(), dp)
            net._sgd_step(ds)
            net._post_iteration()
            return
        step = self._fsdp_train_step if self.fsdp else net._train_step
        with self.mesh:
            net._rng, rng = jax.random.split(net._rng)
            batch = tuple(self._shard_batch(a) for a in (
                ds.features, ds.labels, ds.features_mask, ds.labels_mask))
            (net.params, net.updater_state, net.net_state, loss,
             *_) = step(*step_state(net), batch, rng)
        net.score_value = float(loss)
        net._post_iteration()

    # ------------------------------------------------------------------
    # whole-epoch fusion over the mesh: the SPMD composition of
    # ParallelWrapper's batch sharding with fit_epochs' one-program-per-
    # chunk design (perf/epoch_cache.py) — batch sharded over 'data',
    # params/updater replicated (or FSDP-sharded), GSPMD inserting the
    # per-step gradient all-reduce; still ONE dispatch per epoch chunk
    # at any device count.
    # ------------------------------------------------------------------
    def fused_epochs_supported(self) -> bool:
        """The wrapped network's own fused-path matrix; the wrapper adds
        no further exclusions (the chunk program is the network's)."""
        supported = getattr(self.network, "fused_epochs_supported", None)
        return bool(supported and supported())

    def build_epoch_cache(self, data, accum_steps: Optional[int] = None):
        """HBM dataset cache with every batch SHARDED over the mesh's
        ``data`` axis — each chip holds B/n rows of every batch, so the
        cacheable dataset size scales linearly with chip count.
        ``accum_steps=None`` resolves ``DL4J_ACCUM_STEPS``."""
        return self.network.build_epoch_cache(
            data, mesh=self.mesh, accum_steps=accum_steps)

    def _epoch_program(self, shuffle: bool, accum_steps: int,  # dl4j-lint: disable=adhoc-out-shardings -- shardings sourced from the registry; only the jit pin lives here
                       guard: bool = False, metrics_stride: int = 0):
        """The network's pure chunk program jitted for SPMD execution:
        out_shardings pinned to the registry's per-leaf specs so donated
        params/updater state STAY in their registry layout (replicated,
        TP-sharded, FSDP-sharded, or a composition) across chunks instead
        of whatever the partitioner would pick. With the numeric sentinel
        compiled in (``guard``) the program returns an extra output — the
        ``[E, N]`` trip history — replicated like the loss history; the
        telemetry metrics pack (``metrics_stride``) appends another
        replicated ``[E, N, 4]`` output after it."""
        from deeplearning4j_tpu.monitor.profile import ProfiledProgram
        from deeplearning4j_tpu.parallel.sharding_registry import (
            replicated_sharding)

        key = (shuffle, accum_steps, guard, metrics_stride)
        fn = self._epoch_steps.get(key)
        if fn is None:
            repl = replicated_sharding(self.mesh)
            out = (self._param_shardings, self._upd_shardings, repl, repl)
            if guard:
                out = out + (repl,)
            if metrics_stride:
                out = out + (repl,)
            fn = ProfiledProgram(
                jax.jit(self.network._epoch_run_fn(shuffle, accum_steps,
                                                   guard, metrics_stride),
                        donate_argnums=(0, 1, 2) if self._donate else (),
                        out_shardings=out),
                name="ParallelWrapper", key=key)
            self._epoch_steps[key] = fn
        return fn

    def fit_epochs(self, data, num_epochs: int, *, shuffle: bool = True,
                   chunk_epochs: Optional[int] = None,
                   accum_steps: Optional[int] = None,
                   guard: Optional[str] = None, telemetry=None,
                   on_chunk=None):
        """``fit_epochs`` as ONE donated SPMD program per epoch chunk:
        E epochs x N batches of `lax.scan` with the batch axis sharded
        over the mesh ``data`` axis, params/updater replicated (or
        sharded when ``fsdp=True``), the per-epoch reshuffle permuting
        the unsharded batch-index axis (shard-local gathers, no
        resharding collective) and GSPMD inserting one gradient
        all-reduce per step. ``accum_steps=K`` scans K microbatches per
        updater apply; ``telemetry=`` compiles the in-program metrics
        pack in (an extra replicated ``[E, N, 4]`` output — see
        MultiLayerNetwork.fit_epochs). Returns the ``[E, N]`` loss
        history, or ``None``
        when a fallback ran (unsupported config -> the network's own
        fallback matrix; over-budget dataset -> per-batch streaming
        through ``AsyncDataSetIterator`` device prefetch — sharded via
        the wrapper's step for MultiLayerNetwork, the network's own
        single-device fit for ComputationGraph, which does not speak the
        per-batch sharded-step protocol)."""
        from deeplearning4j_tpu.compile_cache import ensure_compile_cache
        from deeplearning4j_tpu.perf.epoch_cache import (
            DeviceDataSetCache, DeviceMultiDataSetCache,
            accum_steps_default, stream_epochs)

        ensure_compile_cache()
        net = self.network
        net._ensure_init()
        if num_epochs <= 0:
            return None
        if not (getattr(net.conf, "backprop", True)
                or getattr(net.conf, "pretrain", False)):
            return None  # fit() trains nothing in this configuration
        if accum_steps is None:
            accum_steps = accum_steps_default()
        prebuilt = isinstance(data, (DeviceDataSetCache,
                                     DeviceMultiDataSetCache))
        if not self.fused_epochs_supported():
            if prebuilt:
                raise ValueError(
                    "this configuration needs the per-step fit loop — "
                    "pass the original iterator, not a prebuilt cache")
            # the network's own fit_epochs owns the fallback matrix;
            # fsdp has no unsharded fallback (wrapper.fit raises for it)
            if self.fsdp:
                raise ValueError(
                    "ParallelWrapper(fsdp=True) cannot run this "
                    "configuration's per-step fallback; use fsdp=False")
            net.fit_epochs(data, num_epochs, shuffle=shuffle,
                           chunk_epochs=chunk_epochs)
            return None
        cache = data if prebuilt else self.build_epoch_cache(
            data, accum_steps=accum_steps)
        if cache is None:
            # over budget even sharded: stream per batch THROUGH the
            # sharded step (wrapper.fit), link hidden by device prefetch.
            # ComputationGraph has no per-batch sharded step: fsdp=True
            # would raise mid-stream from wrapper.fit, so fail HERE with
            # the actionable levers instead; fsdp=False delegates to the
            # graph's own single-device fit (wrapper.fit logs it).
            from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

            if self.fsdp and not isinstance(net, MultiLayerNetwork):
                raise ValueError(
                    "dataset exceeds the per-shard cache budget and "
                    "ComputationGraph has no fsdp streaming fallback — "
                    "raise DL4J_DEVICE_CACHE_MB, set "
                    "DL4J_CACHE_DTYPE=bfloat16, or increase accum_steps")
            stream_epochs(self, data, num_epochs)
            return None
        return run_fused_epochs(
            net, cache, num_epochs, chunk_epochs, self._epoch_program,
            shuffle=shuffle, accum_steps=accum_steps, guard=guard,
            telemetry=telemetry, on_chunk=on_chunk,
            mesh=lambda: self.mesh,
            reshard=lambda new_mesh: self._apply_reshard(new_mesh, cache))

    def output(self, x):
        x = np.asarray(x)
        if x.shape[0] % self.data_parallelism == 0:
            x = self._shard_batch(x)  # else: unsharded fallback
        with self.mesh:
            return self.network.output(x)

    # -- model-like surface so trainers (early stopping, solvers) can use
    #    the wrapper interchangeably with the wrapped network (the role of
    #    BaseSparkEarlyStoppingTrainer's SparkDl4jMultiLayer handle,
    #    spark/.../BaseSparkEarlyStoppingTrainer.java:301) ---------------
    @property
    def score_value(self) -> float:
        return self.network.score_value

    def score(self, ds) -> float:
        """Scoring forward sharded over the mesh (no host gather: the
        sharded device arrays feed the jitted score fn directly)."""
        net = self.network
        if (ds.num_examples() % self.data_parallelism
                or not hasattr(net, "_score_fn")):
            return net.score(ds)
        with self.mesh:
            val = net._score_fn(
                net.params, net.net_state,
                self._shard_batch(ds.features), self._shard_batch(ds.labels),
                self._shard_batch(ds.features_mask),
                self._shard_batch(ds.labels_mask))
        net.score_value = val
        return net.score_value

    def clone(self):
        return self.network.clone()

    @property
    def conf(self):
        return self.network.conf

    def evaluate(self, data):
        """Distributed evaluation: each batch's forward shards over the
        mesh; per-batch Evaluations merge on host — the reference's
        map-side EvaluateFlatMapFunction + Evaluation.merge reduce
        (SparkDl4jMultiLayer.evaluate :576-607) with the map side compiled.
        Batches whose size does not divide the mesh run unsharded."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        if isinstance(data, DataSet):
            batches = [data]
        else:
            if hasattr(data, "reset"):
                data.reset()
            batches = data
        total = Evaluation()
        for ds in batches:
            out = np.asarray(self.output(ds.features))
            part = Evaluation()
            part.eval(np.asarray(ds.labels), out,
                      mask=None if ds.labels_mask is None
                      else np.asarray(ds.labels_mask))
            total.merge(part)
        return total


class ParameterAveragingTrainer:
    """Reference-parity DP: N independent replicas + periodic averaging.

    Semantics match SparkDl4jMultiLayer with ``averageEachIteration=false``:
    each replica runs ``averaging_frequency`` local updater steps on its own
    shard of every global batch, then params AND updater state are averaged
    across replicas (UpdaterAggregator behavior).
    """

    def __init__(self, network, num_replicas: Optional[int] = None,
                 averaging_frequency: int = 1, mesh: Optional[Mesh] = None):
        network._ensure_init()
        self.network = network
        self.mesh = mesh or build_mesh()
        self.num_replicas = num_replicas or self.mesh.shape[DATA_AXIS]
        self.averaging_frequency = max(1, averaging_frequency)
        self._stacked: Optional[Any] = None  # [R, ...] params
        self._stacked_upd: Optional[Any] = None
        self._local_steps = 0

    # ------------------------------------------------------------------
    def _stack(self, tree):  # dl4j-lint: disable=adhoc-out-shardings -- replica-axis stacking is local-SGD semantics, not model placement; registry axes do not apply
        r = self.num_replicas
        stacked = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (r,) + p.shape), tree)
        # shard the replica axis over 'data' when it divides evenly;
        # otherwise replicate (sharding here is an optimization, not
        # semantics)
        if r % self.mesh.shape[DATA_AXIS] == 0:
            spec = lambda p: P(DATA_AXIS, *([None] * (p.ndim - 1)))
        else:
            spec = lambda p: P()
        return jax.tree_util.tree_map(
            lambda p: jax.device_put(p, NamedSharding(self.mesh, spec(p))), stacked)

    @functools.cached_property
    def _replica_step(self):
        net = self.network
        gc = net.conf.global_conf

        def one_replica(params, upd, state, iteration, x, y):
            def loss_fn(p):
                return net._loss_and_state(p, state, x, y, None, None,
                                           rng=None, train=True)

            (loss, (new_state, _)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            scale = lr_policy_scale(
                gc.lr_policy, iteration, gc.lr_policy_decay_rate,
                gc.lr_policy_steps, gc.lr_policy_power, gc.lr_schedule,
                base_lr=gc.learning_rate)
            new_params, new_upd = {}, {}
            for i, spec in enumerate(net.updater_specs):
                si = str(i)
                steps_i, upd_i = apply_updater(
                    spec, grads[si], upd[si], scale, iteration + 1)
                new_params[si] = jax.tree_util.tree_map(
                    lambda p, s: p - s.astype(p.dtype), params[si], steps_i)
                new_upd[si] = upd_i
            return new_params, new_upd, new_state, loss

        vstep = jax.vmap(one_replica, in_axes=(0, 0, None, None, 0, 0),
                         out_axes=(0, 0, None, 0))

        def step(stacked_params, stacked_upd, state, iteration, xs, ys):
            with dtypes_mod.policy_scope(net._policy):
                return vstep(stacked_params, stacked_upd, state, iteration, xs, ys)

        return jax.jit(step, donate_argnums=(0, 1))

    @functools.cached_property
    def _average(self):
        def avg(stacked):
            return jax.tree_util.tree_map(lambda p: jnp.mean(p, axis=0), stacked)

        return jax.jit(avg)

    # ------------------------------------------------------------------
    def fit(self, data, num_epochs: int = 1):
        net = self.network
        if isinstance(data, DataSet):
            batches = [data]
        else:
            batches = data
        for _ in range(num_epochs):
            if hasattr(batches, "reset"):
                batches.reset()
            for ds in batches:
                self._fit_one(ds)
        self._sync_down(force=True)
        return self

    def _fit_one(self, ds: DataSet):
        net = self.network
        r = self.num_replicas
        n = ds.num_examples()
        if n % r:
            raise ValueError(f"batch {n} not divisible by {r} replicas")
        if self._stacked is None:
            self._stacked = self._stack(net.params)
            self._stacked_upd = self._stack(net.updater_state)
        per = n // r
        xs = jnp.asarray(ds.features).reshape((r, per) + ds.features.shape[1:])
        ys = jnp.asarray(ds.labels).reshape((r, per) + ds.labels.shape[1:])
        with self.mesh:
            self._stacked, self._stacked_upd, net.net_state, losses = (
                self._replica_step(
                    self._stacked, self._stacked_upd, net.net_state,
                    jnp.asarray(net.iteration_count, jnp.int32), xs, ys))
        net.score_value = float(jnp.mean(losses))
        self._local_steps += 1
        if self._local_steps % self.averaging_frequency == 0:
            self._sync_down()
        net._post_iteration()

    def _sync_down(self, force: bool = False):
        """Average replicas → replicated params (+ updater state), restack."""
        if self._stacked is None:
            return
        net = self.network
        with self.mesh:
            net.params = self._average(self._stacked)
            net.updater_state = self._average(self._stacked_upd)
        if force:
            self._stacked = None
            self._stacked_upd = None
        else:
            self._stacked = self._stack(net.params)
            self._stacked_upd = self._stack(net.updater_state)
