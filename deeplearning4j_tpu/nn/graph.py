"""ComputationGraph: DAG networks with multi-input/multi-output.

Functional re-design of ``nn/graph/ComputationGraph.java:68`` (init :214,
topological order :342,606, fit :449-563, computeGradientAndScore :668,
feedForward :701-729) and the vertex impls in ``nn/graph/vertex/impl/``
(LayerVertex, MergeVertex, ElementWiseVertex, SubsetVertex,
LastTimeStepVertex, DuplicateToTimeSeriesVertex).

The whole DAG forward + every output head's loss + backward + updaters
compile into ONE XLA program; vertex dispatch happens at trace time (the
topo order is static), so at runtime there is no graph interpreter at all —
unlike the reference, which walks GraphVertex[] per minibatch.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes as dtypes_mod
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.graph import (
    ComputationGraphConfiguration,
    DuplicateToTimeSeriesVertex,
    ElementWiseVertex,
    GraphVertexConf,
    LastTimeStepVertex,
    MergeVertex,
    PreprocessorVertex,
    ScaleVertex,
    StackVertex,
    SubsetVertex,
    UnstackVertex,
)
from deeplearning4j_tpu.nn.conf.preprocessors import InputPreProcessor
from deeplearning4j_tpu.nn.layers.base import forward_layer, get_layer_impl
from deeplearning4j_tpu.nn.updater import (
    UpdaterSpec,
    flat_apply_safe,
    grouped_apply_updaters,
    init_updater_state,
    lr_policy_scale,
    per_layer_apply_updaters,
)
from deeplearning4j_tpu.ops.losses import compute_loss
from deeplearning4j_tpu.scopes import layer_scope, scope
from deeplearning4j_tpu.perf.bucketing import (
    bucket_size,
    pad_axis0,
    padded_label_mask,
)
from deeplearning4j_tpu.monitor import record_counter
from deeplearning4j_tpu.perf.device_eval import confusion_update
from deeplearning4j_tpu.perf.epoch_cache import (
    DeviceMultiDataSetCache,
    accum_steps_default,
)
from deeplearning4j_tpu.analysis.annotations import traced
from deeplearning4j_tpu.nn.train_step import (
    epoch_run_fn,
    epoch_train_step,
    fit_epochs,
    jit_step,
    multi_step_fn,
    step_state,
    tbptt_fn,
)


def _slice_mds_time(mds: MultiDataSet, start: int, end: int) -> MultiDataSet:
    """Slice every temporal ([b, t, ...]) array to the [start, end) window;
    non-temporal arrays pass through whole."""

    def cut(a):
        return a if a is None or np.ndim(a) < 2 else (
            a[:, start:end] if np.ndim(a) >= 3 else a)

    def cut_mask(m):
        # masks are [b, t]
        return None if m is None else m[:, start:end]

    return MultiDataSet(
        [cut(f) for f in mds.features],
        [cut(l) for l in mds.labels],
        None if mds.features_masks is None
        else [cut_mask(m) for m in mds.features_masks],
        None if mds.labels_masks is None
        else [cut_mask(m) for m in mds.labels_masks],
    )


def _batch_of(mds: MultiDataSet):
    """A MultiDataSet as the train programs' batch pytree ``(inputs,
    labels, feature_masks, label_masks)`` on the device: tuples per
    input / output position, ``None`` for an absent mask."""
    def masks(ms):
        return None if ms is None else tuple(
            None if m is None else jnp.asarray(m) for m in ms)

    return (tuple(jnp.asarray(f) for f in mds.features),
            tuple(jnp.asarray(l) for l in mds.labels),
            masks(mds.features_masks), masks(mds.labels_masks))


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.layer_impls = {n: get_layer_impl(lc) for n, lc in conf.layers.items()}
        self.params: Dict[str, Any] = {}
        self.net_state: Dict[str, Any] = {}
        self.updater_state: Dict[str, Any] = {}
        self.updater_specs: Dict[str, UpdaterSpec] = {}
        self.iteration_count = 0
        self._score = float("nan")
        self.listeners: List[Any] = []
        self._initialized = False
        self._rng = jax.random.PRNGKey(conf.global_conf.seed)
        self._policy = dtypes_mod.policy_from_name(conf.global_conf.dtype_policy)
        self._rnn_state: Dict[str, Any] = {}  # rnnTimeStep carries
        self._eval_readbacks = 0  # host transfers made by evaluate() calls
        self._eval_steps: Dict[int, Any] = {}  # jitted eval per output head
        self._train_dispatches = 0  # train-program launches (bench evidence)
        self._epoch_steps: Dict[Any, Any] = {}  # fused program per (shuffle, K, guard, stride)
        # host LR multiplier — the halve_lr divergence policy's knob (the
        # graph has no SCORE-reactive policy, so this stays 1.0 otherwise)
        self._lr_scale_host = 1.0
        self._last_sentinel = None  # [E, N] trip history of the last fit_epochs
        self._last_metrics = None  # [E, N, 4] metrics-pack history (monitor.pack)
        self._epoch_cursor = 0  # epochs completed (checkpoint/resume cursor)
        self._step_cursor = 0  # batches into the in-progress epoch (per-step path)

    @property
    def score_value(self) -> float:
        return float(self._score)

    @score_value.setter
    def score_value(self, v) -> None:
        self._score = v

    # ------------------------------------------------------------------
    def init(self) -> "ComputationGraph":
        if self._initialized:
            return self
        gc = self.conf.global_conf
        key = jax.random.PRNGKey(gc.seed)
        with dtypes_mod.policy_scope(self._policy):
            for name in sorted(self.layer_impls):
                key, sub = jax.random.split(key)
                impl = self.layer_impls[name]
                self.params[name] = impl.init_params(sub)
                self.net_state[name] = impl.init_state()
        self.updater_specs = {
            n: UpdaterSpec.from_layer_conf(
                lc, gc.learning_rate,
                momentum_schedule=gc.momentum_schedule)
            for n, lc in self.conf.layers.items()
        }
        self.updater_state = {
            n: init_updater_state(spec, self.params[n])
            for n, spec in self.updater_specs.items()
        }
        self._initialized = True
        return self

    def _ensure_init(self):
        if not self._initialized:
            self.init()

    # ------------------------------------------------------------------
    # forward over topo order (pure)
    # ------------------------------------------------------------------
    def _forward(self, params, net_state, inputs: Sequence[jnp.ndarray], *,
                 train: bool, rng, feature_masks: Optional[Sequence] = None,
                 collect: bool = False, rnn_state: Optional[dict] = None):
        """``rnn_state``: {layer_name: {"h": ..., "c": ...}} initial carries
        for recurrent layers (TBPTT windows / rnnTimeStep —
        ComputationGraph.java:489-534,1285). When given, the matching new
        carries are returned alongside the outputs."""
        conf = self.conf
        values: Dict[str, jnp.ndarray] = {}
        masks: Dict[str, Optional[jnp.ndarray]] = {}
        for i, name in enumerate(conf.inputs):
            values[name] = inputs[i]
            masks[name] = None if feature_masks is None else feature_masks[i]
        new_net_state: Dict[str, Any] = {}
        new_rnn_state: Optional[Dict[str, Any]] = (
            {} if rnn_state is not None else None)
        for name in conf.topological_order:
            if name in conf.inputs:
                continue
            in_names = conf.vertex_inputs[name]
            in_vals = [values[n] for n in in_names]
            in_mask = next((masks.get(n) for n in in_names
                            if masks.get(n) is not None), None)
            if name in conf.layers:
                h = in_vals[0]
                lstate = dict(net_state.get(name, {}))
                if rnn_state is not None and name in rnn_state:
                    lstate.update(rnn_state[name])
                h, lstate_out, rng = forward_layer(
                    self.layer_impls[name], name, params[name], h, lstate,
                    pre=conf.preprocessors.get(name), batch=h.shape[0],
                    train=train, rng=rng, mask=in_mask)
                if rnn_state is not None and name in rnn_state:
                    new_rnn_state[name] = {
                        k: lstate_out[k] for k in rnn_state[name]
                    }
                    lstate_out = {k: v for k, v in lstate_out.items()
                                  if k not in rnn_state[name]}
                new_net_state[name] = {
                    k: v for k, v in lstate_out.items()
                    if k in net_state.get(name, {})
                }
                values[name] = h
                masks[name] = in_mask
            else:
                with layer_scope("dsl.vertex", name):
                    values[name] = self._apply_vertex(
                        conf.vertices[name], in_vals, in_names, values,
                        masks)
                masks[name] = in_mask
        if collect:
            return values, new_net_state, new_rnn_state
        return ([values[o] for o in conf.outputs], new_net_state,
                new_rnn_state)

    def _apply_vertex(self, vertex: GraphVertexConf, in_vals, in_names,
                      values, masks):
        if isinstance(vertex, MergeVertex):
            return jnp.concatenate(in_vals, axis=-1)
        if isinstance(vertex, ElementWiseVertex):
            op = vertex.op
            out = in_vals[0]
            for v in in_vals[1:]:
                if op == "Add":
                    out = out + v
                elif op == "Subtract":
                    out = out - v
                elif op == "Product":
                    out = out * v
                elif op == "Max":
                    out = jnp.maximum(out, v)
                elif op == "Average":
                    out = out + v
                else:
                    raise ValueError(f"unknown elementwise op {op}")
            if op == "Average":
                out = out / float(len(in_vals))
            return out
        if isinstance(vertex, SubsetVertex):
            return in_vals[0][..., vertex.from_index:vertex.to_index + 1]
        if isinstance(vertex, LastTimeStepVertex):
            x = in_vals[0]  # [b, t, f]
            mask = None
            if vertex.mask_input is not None:
                mask = masks.get(vertex.mask_input)
            if mask is None:
                return x[:, -1, :]
            # last non-masked step per example
            idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
            return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :]
        if isinstance(vertex, DuplicateToTimeSeriesVertex):
            x = in_vals[0]  # [b, f]
            ref = values[vertex.input_name]
            t = ref.shape[1]
            return jnp.broadcast_to(x[:, None, :], (x.shape[0], t, x.shape[1]))
        if isinstance(vertex, ScaleVertex):
            return in_vals[0] * vertex.scale
        if isinstance(vertex, StackVertex):
            return jnp.concatenate(in_vals, axis=0)
        if isinstance(vertex, UnstackVertex):
            x = in_vals[0]
            n = x.shape[0] // vertex.stack_size
            return x[vertex.from_index * n:(vertex.from_index + 1) * n]
        if isinstance(vertex, PreprocessorVertex):
            p = InputPreProcessor.from_dict(vertex.preprocessor)
            return p.pre_process(in_vals[0])
        raise ValueError(f"unknown vertex {type(vertex).__name__}")

    # ------------------------------------------------------------------
    # loss over all output heads
    # ------------------------------------------------------------------
    def _loss_and_state(self, params, net_state, inputs, labels,
                        feature_masks, label_masks, rng, train: bool,
                        rnn_state=None):
        outs, new_state, new_rnn = self._forward(
            params, net_state, inputs, train=train, rng=rng,
            feature_masks=feature_masks, rnn_state=rnn_state)
        total = 0.0
        with scope("dsl.loss"):
            for i, out_name in enumerate(self.conf.outputs):
                lc = self.conf.layers.get(out_name)
                if lc is None or not hasattr(lc, "loss_function"):
                    continue
                lm = None if label_masks is None else label_masks[i]
                total = total + compute_loss(
                    lc.loss_function, outs[i], labels[i], lm)
            for name, impl in self.layer_impls.items():
                total = total + impl.l1_l2_penalty(params[name])
        return total, (new_state, new_rnn)

    # ------------------------------------------------------------------
    def _lr_scale(self, iteration, lr_scale_host):
        """Effective LR multiplier for ``iteration``: the schedule's
        policy scale times the host scale (``halve_lr`` knob). Shared by
        the updater apply and the telemetry pack's lr-scale column."""
        gc = self.conf.global_conf
        return lr_policy_scale(
            gc.lr_policy, iteration, gc.lr_policy_decay_rate,
            gc.lr_policy_steps, gc.lr_policy_power, gc.lr_schedule,
            base_lr=gc.learning_rate) * lr_scale_host

    def _apply_updaters(self, params, updater_state, grads, iteration,
                        lr_scale_host):
        """LR schedule + updater math + parameter update — the apply
        stage of ``train_step.optimizer_step``. ``lr_scale_host`` (a
        traced scalar) is the host LR multiplier the ``halve_lr``
        divergence policy adjusts. ONE flattened sweep per (spec, lr, dtype) leaf group instead of
        a per-vertex Python loop (``grouped_apply_updaters``; bitwise
        the per-layer math); heterogeneously-sharded state (TP/FSDP
        placements) takes the per-layer apply — a concat over mixed
        shardings would replicate every leaf on every chip (see
        ``flat_apply_safe``). Under the master-weights policy ``params``
        are the f32 masters and ``grads`` arrive already upcast."""
        scale = self._lr_scale(iteration, lr_scale_host)
        items = list(self.updater_specs.items())
        apply_fn = (grouped_apply_updaters
                    if flat_apply_safe(self.params)
                    else per_layer_apply_updaters)
        return apply_fn(items, params, updater_state, grads, scale,
                        iteration + 1)

    @traced
    def _micro_loss(self, params, net_state, batch, rng, d_full, k: int):
        """One micro-batch's share of the FULL batch's training loss (see
        MultiLayerNetwork._micro_loss): every output head's masked sum
        over the full batch's per-head mask denominator ``d_full[i]``,
        plus 1/k of the penalty. Returns ``(loss, new_net_state)``."""
        inputs, labels, feature_masks, label_masks = batch
        outs, new_state, _ = self._forward(
            params, net_state, inputs, train=True, rng=rng,
            feature_masks=feature_masks)
        total = 0.0
        with scope("dsl.loss"):
            for i, out_name in enumerate(self.conf.outputs):
                lc = self.conf.layers.get(out_name)
                if lc is None or not hasattr(lc, "loss_function"):
                    continue
                core = compute_loss(
                    lc.loss_function, outs[i], labels[i], label_masks[i])
                d_mb = jnp.maximum(jnp.sum(label_masks[i]), 1.0)
                total = total + core * (d_mb / d_full[i])
            for name, impl in self.layer_impls.items():
                total = total + impl.l1_l2_penalty(params[name]) / k
        return total, new_state

    @functools.cached_property
    def _train_step(self):
        return jit_step(self)

    @functools.cached_property
    def _multi_train_step(self):
        return jax.jit(multi_step_fn(self), donate_argnums=(0, 1, 2))

    def fit_steps(self, data, n_steps: int):
        """``fit(data)`` called ``n_steps`` times, fused into one XLA
        program (see MultiLayerNetwork.fit_steps: same contract —
        listeners fire once after the block with the final score).
        Falls back to a plain loop for TBPTT/temporal batches."""
        self._ensure_init()
        gc = self.conf.global_conf
        if isinstance(data, DataSet):
            data = MultiDataSet.from_dataset(data)
        from deeplearning4j_tpu.nn.conf.enums import BackpropType

        if (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                and any(np.ndim(f) == 3 for f in data.features)):
            for _ in range(n_steps):
                self.fit(data)
            return self
        total = n_steps * max(1, gc.iterations)
        keys = jax.random.split(self._rng, total + 1)
        self._rng = keys[0]
        (self.params, self.updater_state, self.net_state, _, loss) = (
            self._multi_train_step(*step_state(self), _batch_of(data),
                                   keys[1:], None))
        self._score = loss
        self._train_dispatches += 1
        record_counter("train_dispatches_total", model="ComputationGraph",
                       path="fit_steps")
        self.iteration_count += total
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration_count)
        return self

    # ------------------------------------------------------------------
    # whole-epoch fusion (the ComputationGraph counterpart of
    # MultiLayerNetwork.fit_epochs — see perf/epoch_cache.py)
    # ------------------------------------------------------------------
    def _epoch_run_fn(self, shuffle: bool, accum_steps: int = 1,
                      guard: bool = False, metrics_stride: int = 0):
        """The PURE chunk program ``run(params, updater_state, net_state,
        iteration0, lr_scale_host, xs, ys, fms, lms, epoch_keys)`` over
        this graph — stacks are tuples per input/output position
        (``train_step.epoch_run_fn``)."""
        return epoch_run_fn(self, shuffle, accum_steps, guard,
                            metrics_stride)

    def _epoch_train_step(self, shuffle: bool, accum_steps: int = 1,
                          guard: bool = False, metrics_stride: int = 0):
        """The jitted, donating chunk program for this key, traced once
        and cached in ``_epoch_steps`` (``train_step.epoch_train_step``)."""
        return epoch_train_step(self, shuffle, accum_steps, guard,
                                metrics_stride)

    def fused_epochs_supported(self) -> bool:
        """True when this configuration can run the fused epoch program.
        ComputationGraph's per-step path has no non-SGD solver or
        score-reactive LR handling, so the matrix is narrower than
        MultiLayerNetwork's: TBPTT and ``iterations > 1`` are the only
        fallbacks."""
        from deeplearning4j_tpu.nn.conf.enums import BackpropType

        return (self.conf.backprop_type != BackpropType.TRUNCATED_BPTT
                and max(1, self.conf.global_conf.iterations) == 1)

    def build_epoch_cache(self, data, mesh=None,
                          accum_steps: Optional[int] = None):
        """Prebuild the HBM dataset cache ``fit_epochs`` would build.
        ``mesh`` shards the batch axis over the mesh's ``data`` axis;
        ``accum_steps=None`` resolves ``DL4J_ACCUM_STEPS``."""
        if accum_steps is None:
            accum_steps = accum_steps_default()
        return DeviceMultiDataSetCache.build(data, mesh=mesh,
                                             accum_steps=accum_steps)

    def _place_replicated(self, mesh):
        """Replicate params/updater/net state on ``mesh`` (see
        MultiLayerNetwork._place_replicated)."""
        from deeplearning4j_tpu.parallel.sharding_registry import (
            replicated_sharding)

        repl = replicated_sharding(mesh)
        self.params = jax.device_put(self.params, repl)
        self.updater_state = jax.device_put(self.updater_state, repl)
        self.net_state = jax.device_put(self.net_state, repl)

    def _place_on_mesh(self, mesh):
        """Registry-driven placement: replicate on pure-DP meshes, shard
        tensor-parallel when the mesh has a ``model`` axis (vertex specs
        follow topological order so the Megatron column/row alternation
        tracks dataflow — see MultiLayerNetwork._place_on_mesh)."""
        from deeplearning4j_tpu.parallel.sharding_registry import (
            ShardingRegistry)

        return ShardingRegistry.for_network(self, mesh).place_network(self)

    def request_reshard(self, mesh) -> None:
        """Request a chunk-boundary elastic reshard of the in-flight
        ``fit_epochs`` run (see MultiLayerNetwork.request_reshard)."""
        self._pending_mesh = (mesh,)

    def fit_epochs(self, data, num_epochs: int, *, shuffle: bool = True,
                   chunk_epochs: Optional[int] = None,
                   cache_mb: Optional[float] = None, mesh=None,
                   accum_steps: Optional[int] = None,
                   guard: Optional[str] = None, telemetry=None,
                   on_chunk=None):
        """Whole-epoch fused training over a DataSet/MultiDataSet iterator
        (or a prebuilt ``DeviceMultiDataSetCache``) — same contract as
        MultiLayerNetwork.fit_epochs: one dispatch per chunk, per-epoch
        device-side reshuffle, ``[E, N]`` loss history returned (``None``
        when a fallback ran), ``mesh=``/``accum_steps=`` for SPMD batch
        sharding and gradient accumulation, the in-program numeric
        sentinel under the ``guard`` (``DL4J_NAN_GUARD``) policy with the
        trip history in ``self._last_sentinel``, and
        ``on_chunk(epochs_done) -> bool`` as the chunk-boundary
        checkpoint/preemption hook, and ``telemetry=`` compiling the
        in-program metrics pack in (``[E, N, 4]`` history in
        ``self._last_metrics`` — see MultiLayerNetwork.fit_epochs).
        Falls back to the per-step loop for TBPTT and ``iterations >
        1``; over-budget datasets stream with N-deep async device
        prefetch."""
        return fit_epochs(
            self, data, num_epochs, DeviceMultiDataSetCache,
            "TBPTT / iterations > 1", shuffle=shuffle,
            chunk_epochs=chunk_epochs, cache_mb=cache_mb, mesh=mesh,
            accum_steps=accum_steps, guard=guard, telemetry=telemetry,
            on_chunk=on_chunk)

    @functools.cached_property
    def _output_fn(self):
        def out(params, net_state, inputs):
            with dtypes_mod.policy_scope(self._policy):
                outs, _, _ = self._forward(params, net_state, inputs,
                                           train=False, rng=None)
            return outs

        return jax.jit(out)

    # ------------------------------------------------------------------
    # fit (ComputationGraph.fit :449-563)
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, num_epochs: int = 1):
        self._ensure_init()
        if labels is not None:
            data = MultiDataSet([data] if not isinstance(data, (list, tuple)) else data,
                                [labels] if not isinstance(labels, (list, tuple)) else labels)
        if isinstance(data, DataSet):
            data = MultiDataSet.from_dataset(data)
        if isinstance(data, MultiDataSet):
            self._fit_batches([data])
            return self
        for _ in range(num_epochs):
            if hasattr(data, "reset"):
                data.reset()
            self._fit_batches(data)
        return self

    def _fit_batches(self, batches):
        from deeplearning4j_tpu.nn.conf.enums import BackpropType

        gc = self.conf.global_conf
        for mds in batches:
            if isinstance(mds, DataSet):
                mds = MultiDataSet.from_dataset(mds)
            if (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                    and any(np.ndim(f) == 3 for f in mds.features)):
                self._fit_tbptt(mds)
                continue
            for _ in range(max(1, gc.iterations)):
                self._one_iteration(mds, rnn_state=None)

    def _one_iteration(self, mds: MultiDataSet, rnn_state):
        """One optimizer step; returns the new rnn carry (or None)."""
        self._train_dispatches += 1
        record_counter("train_dispatches_total", model="ComputationGraph",
                       path="per_step")
        self._rng, rng = jax.random.split(self._rng)
        (self.params, self.updater_state, self.net_state, loss, new_rnn,
         _, _) = self._train_step(*step_state(self), _batch_of(mds), rng,
                                  rnn_state)
        self._score = loss  # device scalar; no per-step sync
        self.iteration_count += 1
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration_count)
        return new_rnn

    # ------------------------------------------------------------------
    # truncated BPTT over the DAG (ComputationGraph.java:489-534
    # doTruncatedBPTT; window slicing + carried stop-gradient state)
    # ------------------------------------------------------------------
    @functools.cached_property
    def _tbptt_train_step(self):
        return jax.jit(tbptt_fn(self), donate_argnums=(0, 1, 2))

    def _fit_tbptt(self, mds: MultiDataSet):
        from deeplearning4j_tpu.nn.conf.enums import LearningRatePolicy

        gc = self.conf.global_conf
        t = max(f.shape[1] for f in mds.features if np.ndim(f) == 3)
        window = self.conf.tbptt_fwd_length
        batch = mds.num_examples()
        rnn_state = self._zero_rnn_state(batch)
        n_full = t // window
        # listeners contractually fire once per window with intermediate
        # state — fuse only when that contract is unobservable
        fused_ok = (rnn_state is not None and n_full > 1
                    and max(1, gc.iterations) == 1
                    and gc.lr_policy != LearningRatePolicy.SCORE
                    and not self.listeners)
        start = 0
        if fused_ok:
            head = _slice_mds_time(mds, 0, n_full * window)
            keys = jax.random.split(self._rng, n_full + 1)
            self._rng = keys[0]
            (self.params, self.updater_state, self.net_state, rnn_state,
             loss) = self._tbptt_train_step(
                *step_state(self), _batch_of(head), keys[1:], rnn_state)
            self._score = loss
            self.iteration_count += n_full
            start = n_full * window
        for start in range(start, t, window):
            end = min(start + window, t)
            sub = _slice_mds_time(mds, start, end)
            for _ in range(max(1, gc.iterations)):
                new_rnn = self._one_iteration(sub, rnn_state)
            if new_rnn is not None:
                # stop-gradient across window boundaries (truncation)
                rnn_state = jax.tree_util.tree_map(
                    jax.lax.stop_gradient, new_rnn)

    def _zero_rnn_state(self, batch: int) -> Optional[Dict[str, Any]]:
        state: Dict[str, Any] = {}
        for name, lc in self.conf.layers.items():
            if isinstance(lc, L.ImageLSTM):
                n = lc.hidden_size or lc.n_out
                state[name] = {"h": jnp.zeros((batch, n)),
                               "c": jnp.zeros((batch, n))}
            elif isinstance(lc, (L.GravesLSTM, L.LSTM)):
                n = lc.n_out
                state[name] = {"h": jnp.zeros((batch, n)),
                               "c": jnp.zeros((batch, n))}
            elif isinstance(lc, L.GRU):
                state[name] = {"h": jnp.zeros((batch, lc.n_out))}
        return state or None

    # ------------------------------------------------------------------
    def _batch_bucketable(self) -> bool:
        """Stack/Unstack vertices split or concatenate ALONG the batch
        axis — padding the batch would change their segmentation — so
        bucketing is disabled for graphs containing them (those graphs
        compile per exact shape, the pre-bucketing behavior)."""
        return not any(isinstance(v, (StackVertex, UnstackVertex))
                       for v in self.conf.vertices.values())

    def output(self, *inputs) -> List[jnp.ndarray]:
        self._ensure_init()
        xs = tuple(jnp.asarray(x) for x in inputs)
        if not xs or not self._batch_bucketable() or any(
                x.ndim < 2 for x in xs):
            return self._output_fn(self.params, self.net_state, xs)
        n = xs[0].shape[0]
        b = bucket_size(n)
        outs = self._output_fn(self.params, self.net_state,
                               tuple(pad_axis0(x, b) for x in xs))
        if b == n:
            return outs
        return [o[:n] for o in outs]

    def feed_forward(self, *inputs) -> Dict[str, jnp.ndarray]:
        self._ensure_init()
        with dtypes_mod.policy_scope(self._policy):
            values, _, _ = self._forward(
                self.params, self.net_state,
                tuple(jnp.asarray(x) for x in inputs),
                train=False, rng=None, collect=True)
        return values

    # ------------------------------------------------------------------
    # rnnTimeStep (ComputationGraph.java:1285) — stateful stepping
    # ------------------------------------------------------------------
    def rnn_clear_previous_state(self):
        self._rnn_state = {}

    @functools.cached_property
    def _rnn_step_fn(self):
        """Jitted stateful forward (see MultiLayerNetwork._rnn_step_fn)."""

        def step(params, net_state, xs, rnn_state):
            with dtypes_mod.policy_scope(self._policy):
                outs, _, new_rnn = self._forward(
                    params, net_state, xs, train=False, rng=None,
                    rnn_state=rnn_state)
            return outs, new_rnn

        return jax.jit(step)

    def rnn_time_step(self, *inputs) -> List[jnp.ndarray]:
        """Stateful forward for generation: hidden state carries across
        calls. Inputs may be [b, t, f] or [b, f] (single step); 2D inputs
        get 2D outputs back (reference parity)."""
        self._ensure_init()
        xs = [jnp.asarray(x) for x in inputs]
        single_step = all(x.ndim == 2 for x in xs)
        if single_step:
            xs = [x[:, None, :] for x in xs]
        if not getattr(self, "_rnn_state", None):
            self._rnn_state = self._zero_rnn_state(xs[0].shape[0]) or {}
        outs, new_rnn = self._rnn_step_fn(
            self.params, self.net_state, tuple(xs), self._rnn_state)
        if new_rnn:
            self._rnn_state = new_rnn
        if single_step:
            outs = [o[:, 0, :] if o.ndim == 3 else o for o in outs]
        return outs

    @functools.cached_property
    def _score_fn(self):
        """Jitted whole-DAG scoring forward (was eager op-by-op dispatch;
        bucketed callers compile once per shape bucket)."""

        def score(params, net_state, inputs, labels, fms, lms):
            with dtypes_mod.policy_scope(self._policy):
                loss, _ = self._loss_and_state(
                    params, net_state, inputs, labels, fms, lms,
                    rng=None, train=False)
            return loss

        return jax.jit(score)

    def score(self, mds) -> float:
        self._ensure_init()
        if isinstance(mds, DataSet):
            mds = MultiDataSet.from_dataset(mds)
        inputs = tuple(jnp.asarray(f) for f in mds.features)
        labels = tuple(jnp.asarray(l) for l in mds.labels)
        fms = (None if mds.features_masks is None else tuple(
            None if m is None else jnp.asarray(m)
            for m in mds.features_masks))
        raw_lms = (mds.labels_masks if mds.labels_masks is not None
                   else [None] * len(labels))
        if self._batch_bucketable() and inputs and not any(
                x.ndim < 2 for x in inputs):
            b = bucket_size(inputs[0].shape[0])
            # per-head label masks always materialized: pad rows drop out
            # of every head's mask-weighted loss, one program per bucket
            lms = tuple(padded_label_mask(l, m, b)
                        for l, m in zip(labels, raw_lms))
            inputs = tuple(pad_axis0(x, b) for x in inputs)
            labels = tuple(pad_axis0(l, b) for l in labels)
            fms = (None if fms is None else
                   tuple(None if m is None else pad_axis0(m, b)
                         for m in fms))
        else:
            lms = tuple(None if m is None else jnp.asarray(m)
                        for m in raw_lms)
            if all(m is None for m in lms):
                lms = None
        self._score = self._score_fn(self.params, self.net_state, inputs,
                                     labels, fms, lms)
        return self.score_value

    def _eval_step_for(self, output_index: int):
        """Jitted device-eval kernel for one output head (cached per
        head): forward over the DAG + masked argmax + scatter-add into
        the HBM-resident confusion matrix — the same accumulation path
        as MultiLayerNetwork._eval_step, no logit round-trip."""
        fn = self._eval_steps.get(output_index)
        if fn is None:
            def step(params, net_state, cm, inputs, y, lm):
                with dtypes_mod.policy_scope(self._policy):
                    outs, _, _ = self._forward(params, net_state, inputs,
                                               train=False, rng=None)
                return confusion_update(cm, outs[output_index], y, lm)

            fn = jax.jit(step)
            self._eval_steps[output_index] = fn
        return fn

    def evaluate(self, iterator_or_ds, output_index: int = 0,
                 device_accumulation: bool = True):
        """Classification metrics for one output head. Default path
        accumulates the confusion matrix ON DEVICE across all batches
        (one [C, C] readback per call — see MultiLayerNetwork.evaluate);
        batches pad to shape buckets unless the graph has batch-coupled
        Stack/Unstack vertices. ``device_accumulation=False`` keeps the
        per-batch logit-readback host path."""
        from deeplearning4j_tpu.eval import Evaluation

        self._ensure_init()
        ev = Evaluation()
        batches = iterator_or_ds
        if isinstance(batches, (DataSet, MultiDataSet)):
            batches = [batches]
        elif hasattr(batches, "reset"):
            batches.reset()
        if not device_accumulation:
            for ds in batches:
                if isinstance(ds, DataSet):
                    ds = MultiDataSet.from_dataset(ds)
                outs = self.output(*ds.features)
                lm = None
                if (ds.labels_masks is not None
                        and ds.labels_masks[output_index] is not None):
                    lm = np.asarray(ds.labels_masks[output_index])
                ev.eval(np.asarray(ds.labels[output_index]),
                        np.asarray(outs[output_index]), mask=lm)
            return ev
        step = self._eval_step_for(output_index)
        bucketable = self._batch_bucketable()
        cm = None
        for ds in batches:
            if isinstance(ds, DataSet):
                ds = MultiDataSet.from_dataset(ds)
            xs = tuple(jnp.asarray(f) for f in ds.features)
            y = jnp.asarray(ds.labels[output_index])
            raw_lm = (None if ds.labels_masks is None
                      else ds.labels_masks[output_index])
            n = xs[0].shape[0] if xs else y.shape[0]
            b = bucket_size(n) if bucketable and not any(
                x.ndim < 2 for x in xs) else n
            lm = padded_label_mask(y, raw_lm, b)
            if cm is None:
                cm = jnp.zeros((int(y.shape[-1]),) * 2, jnp.int32)
            cm = step(self.params, self.net_state, cm,
                      tuple(pad_axis0(x, b) for x in xs),
                      pad_axis0(y, b), lm)
        if cm is not None:
            self._eval_readbacks += 1
            record_counter("eval_readbacks_total",
                           model="ComputationGraph", kind="confusion")
            ev.eval_confusion(np.asarray(cm))  # the one host transfer
        return ev

    def num_params(self) -> int:
        self._ensure_init()
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(self.params))

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)

    def clone(self) -> "ComputationGraph":
        from deeplearning4j_tpu.nn.multilayer import copy_model_state

        self._ensure_init()
        other = ComputationGraph(self.conf.clone())
        copy_model_state(self, other)
        return other

    def get_param_table(self) -> Dict[str, np.ndarray]:
        self._ensure_init()
        from deeplearning4j_tpu.nn.multilayer import _named_leaves

        table = {}
        for name in sorted(self.params):
            for path, leaf in _named_leaves(self.params[name]):
                table[f"{name}_{path}"] = np.asarray(leaf)
        return table
