"""MultiLayerNetwork: the primary user API (sequential networks).

Functional re-design of ``nn/multilayer/MultiLayerNetwork.java`` (2,284 LoC —
init :343, fit :1015, feedForward :586-717, backprop :1063-1148,
doTruncatedBPTT :1150, rnnTimeStep :1208, output :1472, predict :1347, param
pack/unpack :940-1013).

Where the reference dispatches each layer op synchronously to ND4J with
hand-written backprop (BaseLayer.java:143), here the ENTIRE optimizer step —
forward, loss (+L1/L2), backward via ``jax.grad``, gradient normalization,
updater math, parameter update — is one jit-compiled XLA program with donated
buffers, so params/updater-state live in HBM across steps and the host only
feeds batches. The mutable ``fit/params/set_params`` surface of the reference
is preserved on top of immutable pytrees (SURVEY hard-part #3).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes as dtypes_mod
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.enums import (
    BackpropType,
    LearningRatePolicy,
    OptimizationAlgorithm,
)
from deeplearning4j_tpu.nn.conf.neural_net import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.preprocessors import apply_preprocessor
from deeplearning4j_tpu.nn.layers.base import forward_layer, get_layer_impl
from deeplearning4j_tpu.nn.updater import (
    UpdaterSpec,
    apply_updater,
    flat_apply_safe,
    grouped_apply_updaters,
    init_updater_state,
    lr_policy_scale,
    per_layer_apply_updaters,
)
from deeplearning4j_tpu.ops.losses import compute_loss
from deeplearning4j_tpu.scopes import scope
from deeplearning4j_tpu.perf.bucketing import (
    bucket_size,
    pad_axis0,
    padded_label_mask,
)
from deeplearning4j_tpu.perf.epoch_cache import (
    DeviceDataSetCache,
    accum_steps_default,
)
from deeplearning4j_tpu.perf.device_eval import (
    RegressionStats,
    confusion_update,
    init_regression_sums,
    regression_update,
)
from deeplearning4j_tpu.analysis.annotations import traced
from deeplearning4j_tpu.monitor import record_counter
from deeplearning4j_tpu.nn.train_step import (
    epoch_run_fn,
    epoch_train_step,
    fit_epochs,
    jit_step,
    multi_step_fn,
    step_state,
    tbptt_fn,
)

_RECURRENT_CONFS = (L.GravesLSTM, L.GravesBidirectionalLSTM, L.GRU, L.LSTM)
_PRETRAIN_CONFS = (L.RBM, L.AutoEncoder, L.RecursiveAutoEncoder)


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = [get_layer_impl(lc) for lc in conf.layers]
        self.params: Dict[str, Any] = {}
        self.net_state: Dict[str, Any] = {}
        self.updater_state: Dict[str, Any] = {}
        self.updater_specs: List[UpdaterSpec] = []
        self.iteration_count = 0
        self._score: Any = float("nan")
        self.listeners: List[Any] = []
        self._rnn_state: Dict[str, Any] = {}  # rnnTimeStep carries
        self._lr_scale_host = 1.0  # SCORE-policy decay, adjusted host-side
        self._initialized = False
        self._rng = jax.random.PRNGKey(conf.global_conf.seed)
        self._policy = dtypes_mod.policy_from_name(conf.global_conf.dtype_policy)
        self._eval_readbacks = 0  # host transfers made by evaluate() calls
        self._train_dispatches = 0  # train-program launches (bench evidence)
        self._epoch_steps: Dict[Any, Any] = {}  # fused program per (shuffle, K, guard, stride)
        self._last_sentinel = None  # [E, N] trip history of the last fit_epochs
        self._last_metrics = None  # [E, N, 4] metrics-pack history (monitor.pack)
        self._epoch_cursor = 0  # epochs completed (checkpoint/resume cursor)
        self._step_cursor = 0  # batches into the in-progress epoch (per-step path)

    @property
    def score_value(self) -> float:
        """Most recent loss. Reading this blocks on the device; the train
        loop stores the raw device scalar so steps pipeline without a
        host-device sync per iteration."""
        return float(self._score)

    @score_value.setter
    def score_value(self, v) -> None:
        self._score = v

    # ------------------------------------------------------------------
    # init (MultiLayerNetwork.init :343)
    # ------------------------------------------------------------------
    def init(self) -> "MultiLayerNetwork":
        if self._initialized:
            return self
        gc = self.conf.global_conf
        key = jax.random.PRNGKey(gc.seed)
        with dtypes_mod.policy_scope(self._policy):
            for i, impl in enumerate(self.layers):
                key, sub = jax.random.split(key)
                self.params[str(i)] = impl.init_params(sub)
                self.net_state[str(i)] = impl.init_state()
        self.updater_specs = [
            UpdaterSpec.from_layer_conf(
                lc, gc.learning_rate,
                momentum_schedule=gc.momentum_schedule)
            for lc in self.conf.layers
        ]
        self.updater_state = {
            str(i): init_updater_state(spec, self.params[str(i)])
            for i, spec in enumerate(self.updater_specs)
        }
        self._initialized = True
        return self

    def _ensure_init(self):
        if not self._initialized:
            self.init()

    # ------------------------------------------------------------------
    # forward (pure) — feedForward :586-717
    # ------------------------------------------------------------------
    def _forward(
        self,
        params,
        net_state,
        x,
        *,
        train: bool,
        rng,
        feature_mask=None,
        rnn_state: Optional[dict] = None,
        collect: bool = False,
    ):
        """Apply preprocessors + layers. Returns (out, new_net_state,
        new_rnn_state, activations?)."""
        batch = x.shape[0]
        activations = [x] if collect else None
        new_net_state = {}
        new_rnn_state = {} if rnn_state is not None else None
        h = x
        for i, impl in enumerate(self.layers):
            si = str(i)
            lstate = dict(net_state.get(si, {}))
            if rnn_state is not None and si in rnn_state:
                lstate.update(rnn_state[si])
            h, lstate_out, rng = forward_layer(
                impl, si, params[si], h, lstate,
                pre=self.conf.input_preprocessors.get(i), batch=batch,
                train=train, rng=rng, mask=feature_mask)
            if rnn_state is not None and si in rnn_state:
                new_rnn_state[si] = {
                    k: lstate_out[k] for k in rnn_state[si]
                }
                for k in rnn_state[si]:
                    lstate_out = {kk: vv for kk, vv in lstate_out.items() if kk not in rnn_state[si]}
            new_net_state[si] = {
                k: v for k, v in lstate_out.items() if k in net_state.get(si, {})
            }
            if collect:
                activations.append(h)
        return h, new_net_state, new_rnn_state, activations

    # ------------------------------------------------------------------
    # loss / score
    # ------------------------------------------------------------------
    @property
    def _output_conf(self):
        last = self.conf.layers[-1]
        if not hasattr(last, "loss_function"):
            raise ValueError("last layer has no loss function (need OutputLayer/LossLayer)")
        return last

    def _loss_and_state(self, params, net_state, x, y, feature_mask, label_mask,
                        rng, train: bool, rnn_state=None):
        out, new_state, new_rnn, _ = self._forward(
            params, net_state, x, train=train, rng=rng,
            feature_mask=feature_mask, rnn_state=rnn_state,
        )
        with scope("dsl.loss"):
            loss = compute_loss(
                self._output_conf.loss_function, out, y, label_mask)
            penalty = 0.0
            for i, impl in enumerate(self.layers):
                penalty = penalty + impl.l1_l2_penalty(params[str(i)])
            total = loss + penalty
        return total, (new_state, new_rnn)

    # ------------------------------------------------------------------
    # the jitted train step (replaces Solver/StochasticGradientDescent +
    # BaseUpdater for the SGD family)
    # ------------------------------------------------------------------
    def _lr_scale(self, iteration, lr_scale_host):
        """Effective LR multiplier for ``iteration``: the schedule's
        policy scale times the host scale (``halve_lr`` knob). Shared by
        the updater apply and the telemetry pack's lr-scale column."""
        gc = self.conf.global_conf
        return lr_policy_scale(
            gc.lr_policy, iteration, gc.lr_policy_decay_rate,
            gc.lr_policy_steps, gc.lr_policy_power, gc.lr_schedule,
            base_lr=gc.learning_rate,
        ) * lr_scale_host

    def _apply_updaters(self, params, updater_state, grads, iteration,
                        lr_scale_host):
        """LR schedule + updater math + parameter update — the apply
        stage of ``train_step.optimizer_step``. ONE flattened sweep per (spec, lr, dtype) leaf group
        instead of a per-layer Python loop, so the traced optimizer tail
        is a fused region whose updater-math op count does not scale
        with depth (``grouped_apply_updaters``; bitwise the per-layer
        math). Heterogeneously-sharded state (tensor-parallel / FSDP
        placements) takes the per-layer apply — a concat over mixed
        shardings would replicate every leaf on every chip (see
        ``flat_apply_safe``); the trace-time gate reads the LIVE params'
        placements, consistent because jit re-traces per sharding.
        Under the master-weights policy ``params`` are the f32 masters
        and ``grads`` arrive already upcast to f32."""
        scale = self._lr_scale(iteration, lr_scale_host)
        items = [(str(i), spec)
                 for i, spec in enumerate(self.updater_specs)]
        apply_fn = (grouped_apply_updaters
                    if flat_apply_safe(self.params)
                    else per_layer_apply_updaters)
        return apply_fn(items, params, updater_state, grads, scale,
                        iteration + 1)

    @traced
    def _micro_loss(self, params, net_state, batch, rng, d_full, k: int):
        """One micro-batch's share of the FULL batch's training loss, for
        the accumulation scan (``train_step.accum_grads``): its masked
        sum over the full batch's mask denominator ``d_full``, plus 1/k
        of the L1/L2 penalty — the k shares sum to ``_loss_and_state``'s
        value. Returns ``(loss, new_net_state)``."""
        x, y, feature_mask, label_mask = batch
        out, new_state, _, _ = self._forward(
            params, net_state, x, train=True, rng=rng,
            feature_mask=feature_mask)
        with scope("dsl.loss"):
            core = compute_loss(
                self._output_conf.loss_function, out, y, label_mask)
            d_mb = jnp.maximum(jnp.sum(label_mask), 1.0)
            pen = 0.0
            for i, impl in enumerate(self.layers):
                pen = pen + impl.l1_l2_penalty(params[str(i)])
            share = core * (d_mb / d_full) + pen / k
        return share, new_state

    @functools.cached_property
    def _train_step(self):
        return jit_step(self)

    @functools.cached_property
    def _multi_train_step(self):
        return jax.jit(multi_step_fn(self), donate_argnums=(0, 1, 2))

    @functools.cached_property
    def _score_fn(self):
        def score(params, net_state, x, y, feature_mask, label_mask):
            with dtypes_mod.policy_scope(self._policy):
                loss, _ = self._loss_and_state(
                    params, net_state, x, y, feature_mask, label_mask,
                    rng=None, train=False,
                )
            return loss

        return jax.jit(score)

    @functools.cached_property
    def _output_fn(self):
        def out(params, net_state, x):
            with dtypes_mod.policy_scope(self._policy):
                o, _, _, _ = self._forward(params, net_state, x, train=False, rng=None)
            return o

        return jax.jit(out)

    # ------------------------------------------------------------------
    # fit (MultiLayerNetwork.fit :1015)
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, feature_mask=None, label_mask=None,
            num_epochs: int = 1):
        """fit(DataSetIterator) / fit(DataSet) / fit(features, labels)."""
        self._ensure_init()
        if labels is not None:
            from deeplearning4j_tpu.datasets.dataset import DataSet

            data = DataSet(data, labels, feature_mask, label_mask)
        if hasattr(data, "features"):  # single DataSet
            batches: Any = [data]
            self._fit_batches(batches)
            return self
        for _ in range(num_epochs):
            if hasattr(data, "reset"):
                data.reset()
            self._fit_batches(data)
        return self

    def _fit_batches(self, batches):
        gc = self.conf.global_conf
        if self.conf.pretrain:
            self.pretrain(batches)
            if hasattr(batches, "reset"):
                batches.reset()
        if not self.conf.backprop:
            return
        algo = gc.optimization_algo
        use_solver = algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT
        for ds in batches:
            if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT and _is_temporal(ds.features):
                self._fit_tbptt(ds)
                continue
            if use_solver:
                self._solver_step(ds)  # runs gc.iterations internally
            else:
                for _ in range(max(1, gc.iterations)):
                    self._sgd_step(ds)
                    self._post_iteration()

    def fit_steps(self, ds, n_steps: int):
        """``fit(ds)`` called ``n_steps`` times, fused: the batch transfers
        once and all ``n_steps · conf.iterations`` SGD iterations run as ONE
        XLA program (``train_step.multi_step_fn``). Listeners fire once, after
        the fused block, with the final score. Falls back to a plain ``fit``
        loop for non-SGD optimizers, TBPTT, pretraining, and the
        score-reactive LR policy (which needs a host decision per step)."""
        self._ensure_init()
        gc = self.conf.global_conf
        if not self.conf.backprop and not self.conf.pretrain:
            return self  # fit() trains nothing in this configuration
        if (gc.optimization_algo != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT
                or (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                    and _is_temporal(ds.features))
                or self.conf.pretrain
                or gc.lr_policy == LearningRatePolicy.SCORE):
            for _ in range(n_steps):
                self.fit(ds)
            return self
        total = n_steps * max(1, gc.iterations)
        keys = jax.random.split(self._rng, total + 1)
        self._rng = keys[0]
        (self.params, self.updater_state, self.net_state, _, loss) = (
            self._multi_train_step(*step_state(self), _batch_of(ds),
                                   keys[1:], None))
        self._score = loss
        self._last_input = ds.features
        self._train_dispatches += 1
        record_counter("train_dispatches_total", model="MultiLayerNetwork",
                       path="fit_steps")
        self.iteration_count += total
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration_count)
        return self

    # ------------------------------------------------------------------
    # whole-epoch fusion: E epochs x N batches as ONE XLA program over an
    # HBM-resident dataset cache (the epoch-level generalization of
    # fit_steps' single-batch fusion — see perf/epoch_cache.py)
    # ------------------------------------------------------------------
    def _epoch_run_fn(self, shuffle: bool, accum_steps: int = 1,
                      guard: bool = False, metrics_stride: int = 0):
        """The PURE chunk program ``run(params, updater_state, net_state,
        iteration0, lr_scale_host, xs, ys, fms, lms, epoch_keys)`` over
        this network (``train_step.epoch_run_fn``)."""
        return epoch_run_fn(self, shuffle, accum_steps, guard,
                            metrics_stride)

    def _epoch_train_step(self, shuffle: bool, accum_steps: int = 1,
                          guard: bool = False, metrics_stride: int = 0):
        """The jitted, donating chunk program for this key, traced once
        and cached in ``_epoch_steps`` (``train_step.epoch_train_step``)."""
        return epoch_train_step(self, shuffle, accum_steps, guard,
                                metrics_stride)

    def fused_epochs_supported(self) -> bool:
        """True when this configuration can run the fused epoch program —
        the ``fit_steps`` fallback matrix. Callers that pre-build a
        ``DeviceDataSetCache`` (EarlyStoppingTrainer) gate on this BEFORE
        paying the drain + HBM transfer."""
        gc = self.conf.global_conf
        return (gc.optimization_algo
                == OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT
                and self.conf.backprop_type != BackpropType.TRUNCATED_BPTT
                and not self.conf.pretrain
                and gc.lr_policy != LearningRatePolicy.SCORE
                and max(1, gc.iterations) == 1)

    def build_epoch_cache(self, data, mesh=None,
                          accum_steps: Optional[int] = None):
        """Prebuild the HBM dataset cache ``fit_epochs`` would build —
        callers that re-run chunks (EarlyStoppingTrainer) pay the drain +
        transfer once. ``mesh`` shards the batch axis over ``data``;
        ``accum_steps=None`` resolves ``DL4J_ACCUM_STEPS`` so the budget's
        working-set term prices the accumulation the run will use."""
        if accum_steps is None:
            accum_steps = accum_steps_default()
        return DeviceDataSetCache.build(data, mesh=mesh,
                                        accum_steps=accum_steps)

    def _place_replicated(self, mesh):
        """Replicate params/updater/net state on ``mesh`` so a sharded
        dataset cache and the trainable state agree on device placement
        (GSPMD then inserts the per-step gradient all-reduce)."""
        from deeplearning4j_tpu.parallel.sharding_registry import (
            replicated_sharding)

        repl = replicated_sharding(mesh)
        self.params = jax.device_put(self.params, repl)
        self.updater_state = jax.device_put(self.updater_state, repl)
        self.net_state = jax.device_put(self.net_state, repl)

    def _place_on_mesh(self, mesh):
        """Place trainable state on ``mesh`` via the sharding registry:
        pure-DP meshes replicate every leaf (GSPMD inserts the gradient
        all-reduce); meshes with a ``model`` axis shard params/updater
        state tensor-parallel per the registry's Megatron layer rules —
        the SAME fused epoch program then runs DP×TP with GSPMD
        propagating the shardings (no out_shardings pinning, so elastic
        reshard to a different topology stays valid)."""
        from deeplearning4j_tpu.parallel.sharding_registry import (
            ShardingRegistry)

        return ShardingRegistry.for_network(self, mesh).place_network(self)

    def request_reshard(self, mesh) -> None:
        """Request a mid-run elastic reshard of the in-flight
        ``fit_epochs`` run: at the NEXT chunk boundary the driver
        snapshots the trainable state to host, re-places it (and the
        dataset cache) on ``mesh`` (``None`` = back to one device), and
        continues — no checkpoint round trip, cursor/RNG/updater state
        carried exactly, final params <= 1e-6 of the uninterrupted run
        (all-reduce summation order only). This is what a goodput
        autopilot's caller-wired ``reshard`` actuator should call; idle
        networks simply apply it on their next fused run."""
        self._pending_mesh = (mesh,)

    def fit_epochs(self, data, num_epochs: int, *, shuffle: bool = True,
                   chunk_epochs: Optional[int] = None,
                   cache_mb: Optional[float] = None, mesh=None,
                   accum_steps: Optional[int] = None,
                   guard: Optional[str] = None, telemetry=None,
                   on_chunk=None):
        """``fit(iterator)`` for ``num_epochs`` epochs with the dataset
        cached in HBM and the whole training run fused: E epochs x N batches
        execute as ONE donated XLA program per chunk (`lax.scan` over a
        per-epoch device-side reshuffle, per-batch RNG keys) — one host
        dispatch and zero re-transfers per chunk instead of E*N of each.
        Returns the ``[E, N]`` per-batch loss history as a device array, or
        ``None`` when a fallback path ran.

        ``data`` may be a DataSetIterator, a list of DataSets, a single
        DataSet, or a prebuilt ``DeviceDataSetCache`` (EarlyStoppingTrainer
        builds one cache and re-runs chunks against it).

        Chunking: listeners/checkpoint hooks need host decision points, so
        with listeners attached the default chunk is ONE epoch (K
        dispatches for K epochs — still N x fewer than streaming); without
        them the whole run is a single program. ``chunk_epochs`` overrides.

        Mesh-aware: ``mesh=`` (or a prebuilt cache carrying one) shards
        every batch over the mesh ``data`` axis and replicates
        params/updater state on it — the chunk runs as ONE donated SPMD
        program with GSPMD inserting the per-step gradient all-reduce
        (use ``ParallelWrapper.fit_epochs`` for FSDP-sharded state).
        ``accum_steps=K`` (default ``DL4J_ACCUM_STEPS``) runs each batch
        as K accumulated microbatches with a single updater apply.

        Self-healing: every fused step runs under the in-program numeric
        sentinel unless ``guard`` (default: the ``DL4J_NAN_GUARD`` env
        policy, default ``skip``) is ``"off"`` — a non-finite loss or
        gradient skips that step in-program (params/updater state carried
        unchanged), the ``[E, N]`` trip history lands in
        ``self._last_sentinel``, and the policy is enforced per chunk
        (``skip`` logs, ``halve_lr`` halves the host LR scale, ``raise``
        replays the chunk per-step from the last-good snapshot and raises
        ``TrainingDivergedError`` naming the epoch/step/batch).
        ``on_chunk(epochs_done) -> bool`` fires at every chunk boundary
        (True stops the run) — the preemption-safe checkpoint hook. The
        per-step fallback paths are NOT sentinel-guarded.

        Telemetry: ``telemetry`` (default: the ``DL4J_TELEMETRY`` /
        ``DL4J_TELEMETRY_STRIDE`` env resolution — off unless opted in)
        compiles the in-program metrics pack into the fused step: an
        ``[E, N, 4]`` history of grad/update/param global-norms + lr
        scale lands in ``self._last_metrics`` and flows to listeners'
        ``chunk_done`` per chunk. ``False``/``0`` compiles it out
        (bitwise the pre-telemetry program), ``True``/an int selects the
        stride. The pack is observational — params are bitwise-identical
        either way.

        Fallbacks (same matrix as ``fit_steps``): non-SGD solvers, TBPTT,
        pretraining, the score-reactive LR policy, and ``iterations > 1``
        run the plain per-step loop; datasets over the HBM budget
        (``DL4J_DEVICE_CACHE_MB``) stream through an N-deep async device
        prefetch instead (``DL4J_PREFETCH_DEPTH``)."""
        self._ensure_init()
        if not self.conf.backprop and not self.conf.pretrain:
            return None  # fit() trains nothing in this configuration
        return fit_epochs(
            self, data, num_epochs, DeviceDataSetCache,
            "non-SGD solver / TBPTT / pretraining / SCORE policy",
            shuffle=shuffle, chunk_epochs=chunk_epochs, cache_mb=cache_mb,
            mesh=mesh, accum_steps=accum_steps, guard=guard,
            telemetry=telemetry, on_chunk=on_chunk)

    def _sgd_step(self, ds, rnn_state=None):
        self._train_dispatches += 1
        record_counter("train_dispatches_total", model="MultiLayerNetwork",
                       path="per_step")
        self._rng, rng = jax.random.split(self._rng)
        (self.params, self.updater_state, self.net_state, loss, new_rnn,
         _, _) = self._train_step(*step_state(self), _batch_of(ds), rng,
                                  rnn_state)
        self._score = loss  # device scalar; no sync (see score_value)
        self._last_input = ds.features  # host ref for UI activation listeners
        return new_rnn

    def _solver_step(self, ds):
        from deeplearning4j_tpu.optimize.solver import Solver

        Solver(self).optimize(ds)

    def _post_iteration(self):
        self.iteration_count += 1
        gc = self.conf.global_conf
        if (gc.lr_policy == LearningRatePolicy.SCORE
                and gc.lr_score_based_decay_rate > 0):
            if getattr(self, "_best_score", None) is None or self.score_value < self._best_score:
                self._best_score = self.score_value
            elif self.score_value > self._best_score:
                self._lr_scale_host *= (1.0 - gc.lr_score_based_decay_rate)
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration_count)

    # ------------------------------------------------------------------
    # truncated BPTT (doTruncatedBPTT :1150)
    # ------------------------------------------------------------------
    @functools.cached_property
    def _tbptt_train_step(self):
        return jax.jit(tbptt_fn(self), donate_argnums=(0, 1, 2))

    def _fit_tbptt(self, ds):
        gc = self.conf.global_conf
        t = ds.features.shape[1]
        window = self.conf.tbptt_fwd_length
        rnn_state = self._zero_rnn_state(ds.features.shape[0])
        n_full = t // window
        # fused path: scan over the full windows in one program. Engaged
        # only when it is OBSERVATIONALLY identical to the host loop:
        # plain SGD, iterations == 1, non-score-reactive LR policy, and no
        # listeners (listeners contractually fire once per window with the
        # intermediate state, which a fused program cannot replay)
        fused_ok = (rnn_state is not None and n_full > 1
                    and max(1, gc.iterations) == 1
                    and gc.lr_policy != LearningRatePolicy.SCORE
                    and not self.listeners)
        start = 0
        if fused_ok:
            keys = jax.random.split(self._rng, n_full + 1)
            self._rng = keys[0]
            (self.params, self.updater_state, self.net_state, rnn_state,
             loss) = self._tbptt_train_step(
                *step_state(self),
                _batch_of(ds.slice_time(0, n_full * window)), keys[1:],
                rnn_state)
            self._score = loss
            self._last_input = ds.features
            self.iteration_count += n_full
            start = n_full * window
        for start in range(start, t, window):
            end = min(start + window, t)
            sub = ds.slice_time(start, end)
            for _ in range(max(1, gc.iterations)):
                new_rnn = self._sgd_step(sub, rnn_state=rnn_state)
                self._post_iteration()
            if new_rnn is not None:
                # stop-gradient across window boundaries (truncation)
                rnn_state = jax.tree_util.tree_map(jax.lax.stop_gradient, new_rnn)

    def _zero_rnn_state(self, batch: int) -> Dict[str, Any]:
        state: Dict[str, Any] = {}
        for i, lc in enumerate(self.conf.layers):
            if isinstance(lc, L.ImageLSTM):
                n = lc.hidden_size or lc.n_out
                state[str(i)] = {"h": jnp.zeros((batch, n)),
                                 "c": jnp.zeros((batch, n))}
            elif isinstance(lc, (L.GravesLSTM, L.LSTM)):
                n = lc.n_out
                state[str(i)] = {"h": jnp.zeros((batch, n)), "c": jnp.zeros((batch, n))}
            elif isinstance(lc, L.GRU):
                state[str(i)] = {"h": jnp.zeros((batch, lc.n_out))}
        return state or None

    # ------------------------------------------------------------------
    # layerwise pretraining (pretrain :159)
    # ------------------------------------------------------------------
    def pretrain(self, batches):
        self._ensure_init()
        from deeplearning4j_tpu.nn.layers.pretrain import AutoEncoderImpl, RBMImpl

        batch_list = list(batches)
        for i, impl in enumerate(self.layers):
            if not isinstance(self.conf.layers[i], _PRETRAIN_CONFS):
                continue
            spec = self.updater_specs[i]
            si = str(i)

            if isinstance(impl, RBMImpl):
                def step(p, s, x, rng, _impl=impl, _spec=spec):
                    grads, score = _impl.pretrain_grads(p, x, rng)
                    steps_i, s2 = apply_updater(
                        _spec, grads, s, jnp.asarray(1.0), jnp.asarray(1))
                    p2 = jax.tree_util.tree_map(lambda a, b: a - b.astype(a.dtype), p, steps_i)
                    return p2, s2, score
            else:
                def step(p, s, x, rng, _impl=impl, _spec=spec):
                    score, grads = jax.value_and_grad(
                        lambda pp: _impl.pretrain_loss(pp, x, rng))(p)
                    steps_i, s2 = apply_updater(
                        _spec, grads, s, jnp.asarray(1.0), jnp.asarray(1))
                    p2 = jax.tree_util.tree_map(lambda a, b: a - b.astype(a.dtype), p, steps_i)
                    return p2, s2, score

            jstep = jax.jit(step, donate_argnums=(0, 1))
            p, s = self.params[si], self.updater_state[si]
            for ds in batch_list:
                x = _dev(ds.features)
                # propagate input through the already-pretrained stack below
                x = self._activate_to_layer(x, i)
                self._rng, rng = jax.random.split(self._rng)
                p, s, score = jstep(p, s, x, rng)
                self.score_value = float(score)
            self.params[si], self.updater_state[si] = p, s

    def _activate_to_layer(self, x, stop: int):
        """Forward through layers [0, stop) without training."""
        if stop == 0:
            return x
        h = x
        # entry minibatch size, NOT h.shape[0]: a mid-stack FF→RNN unfold
        # must use the original batch (h may be time-folded [b*t, f] there)
        batch = x.shape[0]
        for i in range(stop):
            pre = self.conf.input_preprocessors.get(i)
            if pre is not None:
                h, _ = apply_preprocessor(pre, h, batch=batch)
            h, _ = self.layers[i].forward(
                self.params[str(i)], h, dict(self.net_state.get(str(i), {})),
                train=False, rng=None)
        return h

    # ------------------------------------------------------------------
    # inference / scoring (output :1472, predict :1347, score)
    #
    # Every entry point pads the batch axis up the shape-bucket ladder
    # (perf/bucketing) before hitting its jitted program, so a stream of
    # ragged batch sizes compiles once per BUCKET, not once per shape —
    # a recompile costs seconds. Pad rows are row-independent through the
    # forward pass and sliced off (output/predict) or masked out of the
    # reduction (score/evaluate).
    # ------------------------------------------------------------------
    def output(self, x, train: bool = False):
        self._ensure_init()
        x = _dev(x)
        if x.ndim < 2:
            return self._output_fn(self.params, self.net_state, x)
        n = x.shape[0]
        out = self._output_fn(self.params, self.net_state,
                              pad_axis0(x, bucket_size(n)))
        return out[:n] if out.shape[0] != n else out

    def feed_forward(self, x) -> List[jnp.ndarray]:
        """All layer activations, input first (feedForward :586)."""
        self._ensure_init()
        with dtypes_mod.policy_scope(self._policy):
            _, _, _, acts = self._forward(
                self.params, self.net_state, _dev(x), train=False, rng=None,
                collect=True)
        return acts

    @functools.cached_property
    def _predict_fn(self):
        def pred(params, net_state, x):
            with dtypes_mod.policy_scope(self._policy):
                o, _, _, _ = self._forward(params, net_state, x,
                                           train=False, rng=None)
            return jnp.argmax(o, axis=-1).astype(jnp.int32)

        return jax.jit(pred)

    def predict(self, x) -> np.ndarray:
        """Class indices. The argmax runs ON DEVICE so the host transfer
        is [B] int32, not [B, C] f32 logits."""
        self._ensure_init()
        x = _dev(x)
        if x.ndim < 2:
            return np.asarray(self._predict_fn(self.params, self.net_state, x))
        n = x.shape[0]
        idx = self._predict_fn(self.params, self.net_state,
                               pad_axis0(x, bucket_size(n)))
        return np.asarray(idx[:n])

    def score(self, ds=None, x=None, y=None) -> float:
        self._ensure_init()
        if ds is not None:
            x, y = ds.features, ds.labels
            fm, lm = ds.features_mask, ds.labels_mask
        else:
            fm = lm = None
        x, y = _dev(x), _dev(y)
        # the label mask is ALWAYS materialized (ones when absent): pad
        # rows drop out of the mask-weighted loss mean, and masked and
        # unmasked callers share one compiled program per bucket
        b = bucket_size(x.shape[0])
        lm = padded_label_mask(y, lm, b)
        val = self._score_fn(self.params, self.net_state, pad_axis0(x, b),
                             pad_axis0(y, b), pad_axis0(_dev(fm), b), lm)
        self._score = val
        return self.score_value

    def score_examples(self, ds):
        """Per-example losses (ScoreExamplesFunction parity)."""
        from deeplearning4j_tpu.ops.losses import per_example_loss

        out = self.output(ds.features)
        return np.asarray(per_example_loss(
            self._output_conf.loss_function, out, _dev(ds.labels)))

    @functools.cached_property
    def _eval_step(self):
        """Jitted scoring kernel: forward + masked argmax + scatter-add
        into the device confusion matrix. ``cm`` stays in HBM across the
        whole iterator — the only thing evaluate() ever transfers back is
        the final [C, C] int32 grid."""

        def step(params, net_state, cm, x, y, lm):
            with dtypes_mod.policy_scope(self._policy):
                out, _, _, _ = self._forward(params, net_state, x,
                                             train=False, rng=None)
            return confusion_update(cm, out, y, lm)

        return jax.jit(step)

    def evaluate(self, iterator_or_ds, device_accumulation: bool = True):
        """Classification metrics over a DataSet or iterator.

        Default path accumulates ON DEVICE: per batch, one jitted program
        (compiled once per shape bucket) argmaxes logits and labels and
        scatter-adds into a [C, C] confusion matrix resident in HBM; the
        host sees exactly ONE transfer per call — the final count grid —
        instead of per-batch [B, C] f32 logits over the 37 MB/s link.
        ``device_accumulation=False`` keeps the host path (per-batch logit
        readback + vectorized numpy accumulation) for parity testing and
        the bench comparison."""
        from deeplearning4j_tpu.eval import Evaluation

        self._ensure_init()
        ev = Evaluation()
        if not device_accumulation:
            for ds in _as_batches(iterator_or_ds):
                out = self.output(ds.features)
                ev.eval(np.asarray(ds.labels), np.asarray(out),
                        mask=None if ds.labels_mask is None
                        else np.asarray(ds.labels_mask))
            return ev
        cm = None
        for ds in _as_batches(iterator_or_ds):
            x, y = _dev(ds.features), _dev(ds.labels)
            b = bucket_size(x.shape[0])
            lm = padded_label_mask(y, ds.labels_mask, b)
            if cm is None:
                cm = jnp.zeros((int(y.shape[-1]),) * 2, jnp.int32)
            cm = self._eval_step(self.params, self.net_state, cm,
                                 pad_axis0(x, b), pad_axis0(y, b), lm)
        if cm is not None:
            self._eval_readbacks += 1
            record_counter("eval_readbacks_total",
                           model="MultiLayerNetwork", kind="confusion")
            ev.eval_confusion(np.asarray(cm))  # the one host transfer
        return ev

    def evaluate_regression(self, iterator_or_ds) -> RegressionStats:
        """Per-column regression stats with the same device-resident
        discipline as ``evaluate``: sufficient statistics (1+7·C floats)
        accumulate in HBM and transfer once per call."""
        self._ensure_init()
        step = self._regression_eval_step
        sums = None
        for ds in _as_batches(iterator_or_ds):
            x, y = _dev(ds.features), _dev(ds.labels)
            b = bucket_size(x.shape[0])
            lm = padded_label_mask(y, ds.labels_mask, b)
            if sums is None:
                sums = init_regression_sums(int(y.shape[-1]))
            sums = step(self.params, self.net_state, sums,
                        pad_axis0(x, b), pad_axis0(y, b), lm)
        if sums is None:
            sums = init_regression_sums(0)
        else:
            self._eval_readbacks += 1
            record_counter("eval_readbacks_total",
                           model="MultiLayerNetwork", kind="regression")
        return RegressionStats(jax.device_get(sums))

    @functools.cached_property
    def _regression_eval_step(self):
        def step(params, net_state, sums, x, y, lm):
            with dtypes_mod.policy_scope(self._policy):
                out, _, _, _ = self._forward(params, net_state, x,
                                             train=False, rng=None)
            return regression_update(sums, out, y, lm)

        return jax.jit(step)

    def f1_score(self, ds) -> float:
        return self.evaluate(ds).f1()

    # ------------------------------------------------------------------
    # rnnTimeStep (:1208) — stateful stepping for generation
    # ------------------------------------------------------------------
    def rnn_clear_previous_state(self):
        self._rnn_state = {}

    @functools.cached_property
    def _rnn_step_fn(self):
        """Jitted stateful forward: one compiled program per (shape,
        state-structure) signature instead of eager per-op dispatch every
        generation step (round-2 advisor: rnn_time_step ran op-by-op)."""

        def step(params, net_state, x, rnn_state):
            with dtypes_mod.policy_scope(self._policy):
                out, _, new_rnn, _ = self._forward(
                    params, net_state, x, train=False, rng=None,
                    rnn_state=rnn_state)
            return out, new_rnn

        return jax.jit(step)

    def rnn_time_step(self, x):
        """x: [b, t, f] (or [b, f] for one step). Carries hidden state across
        calls like BaseRecurrentLayer.stateMap."""
        self._ensure_init()
        x = _dev(x)
        single_step = x.ndim == 2
        if single_step:
            x = x[:, None, :]
        if not self._rnn_state:
            self._rnn_state = self._zero_rnn_state(x.shape[0]) or {}
        out, new_rnn = self._rnn_step_fn(
            self.params, self.net_state, x, self._rnn_state)
        if new_rnn:
            self._rnn_state = new_rnn
        if single_step and out.ndim == 3:
            out = out[:, 0, :]  # [b, f] in → [b, out] out (reference parity)
        return out

    # ------------------------------------------------------------------
    # params surface (pack/unpack :940-1013)
    # ------------------------------------------------------------------
    def num_params(self) -> int:
        self._ensure_init()
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(self.params))

    def get_flat_params(self) -> np.ndarray:
        """Flatten in deterministic (layer, sorted-param-name) order — the
        analogue of the reference's single flat param vector."""
        self._ensure_init()
        leaves = []
        for i in range(len(self.layers)):
            sub = self.params[str(i)]
            leaves.extend(_sorted_leaves(sub))
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate([np.asarray(l).ravel() for l in leaves])

    def set_flat_params(self, flat: np.ndarray) -> None:
        self._ensure_init()
        flat = np.asarray(flat)
        offset = 0
        new_params = {}
        for i in range(len(self.layers)):
            sub = self.params[str(i)]
            new_sub, offset = _unflatten_like(sub, flat, offset)
            new_params[str(i)] = new_sub
        if offset != flat.size:
            raise ValueError(f"param vector length {flat.size} != expected {offset}")
        self.params = new_params

    def get_param_table(self) -> Dict[str, np.ndarray]:
        """Flat "0_W"-style param table (MultiLayerNetwork.java:1114 naming)."""
        self._ensure_init()
        table = {}
        for i in range(len(self.layers)):
            for path, leaf in _named_leaves(self.params[str(i)]):
                table[f"{i}_{path}"] = np.asarray(leaf)
        return table

    def set_param_table(self, table: Dict[str, np.ndarray]) -> None:
        self._ensure_init()
        for key, value in table.items():
            idx, path = key.split("_", 1)
            _set_by_path(self.params[idx], path, jnp.asarray(value))

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)

    def clone(self) -> "MultiLayerNetwork":
        self._ensure_init()
        other = MultiLayerNetwork(self.conf.clone())
        copy_model_state(self, other)
        return other


def copy_model_state(src, dst) -> None:
    """Deep-copy trained state into a freshly-built network (shared by both
    network classes' clone()). jnp.copy, not aliasing: the live net's train
    step DONATES its buffers, which would delete aliased arrays out from
    under the clone."""
    dst.init()
    dst.params = jax.tree_util.tree_map(jnp.copy, src.params)
    dst.net_state = jax.tree_util.tree_map(jnp.copy, src.net_state)
    dst.updater_state = jax.tree_util.tree_map(jnp.copy, src.updater_state)
    dst.iteration_count = src.iteration_count


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _dev(x):
    if x is None:
        return None
    return jnp.asarray(x)


def _batch_of(ds):
    """A DataSet as the train programs' batch pytree ``(features, labels,
    feature_mask, label_mask)`` on the device."""
    return (_dev(ds.features), _dev(ds.labels), _dev(ds.features_mask),
            _dev(ds.labels_mask))


def _is_temporal(x) -> bool:
    return getattr(x, "ndim", 0) == 3


def _as_batches(it):
    if hasattr(it, "features"):
        return [it]
    if hasattr(it, "reset"):
        it.reset()
    return it


def _sorted_leaves(tree, prefix=""):
    out = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.extend(_sorted_leaves(tree[k]))
    else:
        out.append(tree)
    return out


def _named_leaves(tree, prefix=""):
    out = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            sub_prefix = f"{prefix}.{k}" if prefix else k
            out.extend(_named_leaves(tree[k], sub_prefix))
    else:
        out.append((prefix, tree))
    return out


def _unflatten_like(tree, flat, offset):
    if isinstance(tree, dict):
        new = {}
        for k in sorted(tree):
            new[k], offset = _unflatten_like(tree[k], flat, offset)
        return new, offset
    size = int(np.prod(tree.shape)) if tree.shape else 1
    chunk = flat[offset:offset + size].reshape(tree.shape)
    return jnp.asarray(chunk, tree.dtype), offset + size


def _set_by_path(tree, path, value):
    parts = path.split(".")
    for p in parts[:-1]:
        tree = tree[p]
    tree[parts[-1]] = value
