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
from deeplearning4j_tpu.nn.conf.preprocessors import (
    InputPreProcessor,
    apply_preprocessor,
)
from deeplearning4j_tpu.nn.layers.base import get_layer_impl
from deeplearning4j_tpu.nn.updater import (
    UpdaterSpec,
    flat_apply_safe,
    grouped_apply_updaters,
    init_updater_state,
    lr_policy_scale,
    per_layer_apply_updaters,
)
from deeplearning4j_tpu.ops.losses import compute_loss
from deeplearning4j_tpu.perf.bucketing import (
    bucket_size,
    pad_axis0,
    padded_label_mask,
)
from deeplearning4j_tpu.monitor import fused_metrics_stride, record_counter
from deeplearning4j_tpu.perf.device_eval import confusion_update
from deeplearning4j_tpu.perf.epoch_cache import (
    DeviceMultiDataSetCache,
    accum_steps_default,
    drive_epoch_chunks,
    effective_accum_steps,
    elastic_reshard,
    epoch_schedule,
    stream_epochs,
)
from deeplearning4j_tpu.analysis.annotations import traced


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
                impl = self.layer_impls[name]
                h = in_vals[0]
                batch = h.shape[0]
                pre = conf.preprocessors.get(name)
                if pre is not None:
                    h, rng = apply_preprocessor(pre, h, batch=batch, rng=rng)
                sub_rng = None
                if rng is not None:
                    rng, sub_rng = jax.random.split(rng)
                mask = in_mask if h.ndim == 3 else None
                lstate = dict(net_state.get(name, {}))
                if rnn_state is not None and name in rnn_state:
                    lstate.update(rnn_state[name])
                h, lstate_out = impl.forward(
                    params[name], h, lstate,
                    train=train, rng=sub_rng, mask=mask)
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
                values[name] = self._apply_vertex(
                    conf.vertices[name], in_vals, in_names, values, masks)
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
        for i, out_name in enumerate(self.conf.outputs):
            lc = self.conf.layers.get(out_name)
            if lc is None or not hasattr(lc, "loss_function"):
                continue
            lm = None if label_masks is None else label_masks[i]
            total = total + compute_loss(lc.loss_function, outs[i], labels[i], lm)
        for name, impl in self.layer_impls.items():
            total = total + impl.l1_l2_penalty(params[name])
        return total, (new_state, new_rnn)

    # ------------------------------------------------------------------
    def _lr_scale(self, iteration, lr_scale_host=None):
        """Effective LR multiplier for ``iteration`` (policy scale times
        the host ``halve_lr`` knob when given). Shared by the updater
        apply and the telemetry pack's lr-scale column."""
        gc = self.conf.global_conf
        scale = lr_policy_scale(
            gc.lr_policy, iteration, gc.lr_policy_decay_rate,
            gc.lr_policy_steps, gc.lr_policy_power, gc.lr_schedule,
            base_lr=gc.learning_rate)
        if lr_scale_host is not None:
            scale = scale * lr_scale_host
        return scale

    def _apply_updaters(self, params, updater_state, grads, iteration,
                        lr_scale_host=None):
        """LR schedule + updater math + parameter update — the tail
        every optimizer-step variant (plain, accumulated, guarded)
        shares. ``lr_scale_host`` (a traced scalar, or None = 1) is the
        host LR multiplier the ``halve_lr`` divergence policy adjusts.
        ONE flattened sweep per (spec, lr, dtype) leaf group instead of
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
    def _loss_grads(self, params, net_state, inputs, labels,
                    feature_masks, label_masks, rng, rnn_state=None):
        """Training loss + gradients (pure; caller wraps the dtype policy
        scope). Shared by the plain step and the sentinel-guarded step,
        which needs the grads BEFORE deciding whether to apply them."""
        def loss_fn(p):
            return self._loss_and_state(
                p, net_state, inputs, labels, feature_masks,
                label_masks, rng, train=True, rnn_state=rnn_state)

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    @traced
    def _step_impl(self, params, updater_state, net_state, iteration,
                   inputs, labels, feature_masks, label_masks, rng,
                   rnn_state):
        """One optimizer step (pure; shared by the per-batch jitted step
        and the fused TBPTT scan body)."""
        with dtypes_mod.policy_scope(self._policy):
            # master-weights policy: ONE bf16 copy for forward/backward,
            # grads upcast ONCE, updater applies to the f32 masters
            fwd_params = self._policy.compute_copy(params)
            (loss, (new_net_state, new_rnn)), grads = self._loss_grads(
                fwd_params, net_state, inputs, labels, feature_masks,
                label_masks, rng, rnn_state)
            grads = self._policy.master_grads(grads)
            new_params, new_updater = self._apply_updaters(
                params, updater_state, grads, iteration)
        return new_params, new_updater, new_net_state, loss, new_rnn

    @traced
    def _accum_loss_grads(self, params, net_state, inputs, labels,
                          feature_masks, label_masks, rng,
                          accum_steps: int):
        """Accumulated-microbatch loss + summed gradients (pure; caller
        wraps the dtype policy scope and applies the updater). Returns
        ``(grads, loss, new_net_state)``."""
        k = accum_steps
        micro = inputs[0].shape[0] // k

        def split(a):
            # strided (row i -> microbatch i % k): shard-local under
            # a batch-sharded mesh (see MLN._accum_step_impl)
            if a is None:
                return None
            return jnp.moveaxis(
                a.reshape((micro, k) + a.shape[1:]), 1, 0)

        d_full = tuple(jnp.maximum(jnp.sum(m), 1.0)
                       for m in label_masks)
        seq = {"x": tuple(split(a) for a in inputs),
               "y": tuple(split(a) for a in labels),
               "lm": tuple(split(a) for a in label_masks),
               "rng": jax.random.split(rng, k)}
        if feature_masks is not None:
            seq["fm"] = tuple(split(a) for a in feature_masks)

        def micro_loss(p, nst_in, xm, ym, fmm, lmm, r):
            outs, st, _ = self._forward(
                p, nst_in, xm, train=True, rng=r,
                feature_masks=fmm)
            total = 0.0
            for i, out_name in enumerate(self.conf.outputs):
                lc = self.conf.layers.get(out_name)
                if lc is None or not hasattr(lc, "loss_function"):
                    continue
                core = compute_loss(
                    lc.loss_function, outs[i], ym[i], lmm[i])
                d_mb = jnp.maximum(jnp.sum(lmm[i]), 1.0)
                total = total + core * (d_mb / d_full[i])
            for name, impl in self.layer_impls.items():
                total = total + impl.l1_l2_penalty(p[name]) / k
            return total, st

        def body(carry, inp):
            gsum, lsum, nst_in = carry
            # grads wrt params only; net_state threads through the
            # carry so no microbatch's state update is dropped.
            # Accumulation buffers carry the PARAM dtype (bf16 micro-
            # batch grads upcast into the f32 sum — see MLN counterpart)
            (lval, st), g = jax.value_and_grad(
                micro_loss, has_aux=True)(
                params, nst_in, inp["x"], inp["y"], inp.get("fm"),
                inp["lm"], inp["rng"])
            gsum = jax.tree_util.tree_map(
                lambda s, gg: s + gg.astype(s.dtype), gsum, g)
            return (gsum, lsum + lval, st), None

        zeros = self._policy.grad_zeros(params)
        (grads, loss, new_net_state), _ = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32), net_state), seq)
        return grads, loss, new_net_state

    @traced
    def _accum_step_impl(self, params, updater_state, net_state, iteration,
                         inputs, labels, feature_masks, label_masks, rng,
                         accum_steps: int):
        """One optimizer step over the full batch via ``accum_steps``
        accumulated microbatches (the ComputationGraph counterpart of
        MultiLayerNetwork._accum_step_impl): every output head's
        microbatch loss is its masked SUM over the FULL batch's per-head
        mask denominator (plus 1/K of the penalty), so the summed
        gradients equal the unaccumulated step up to f32 summation
        order. One updater apply."""
        with dtypes_mod.policy_scope(self._policy):
            grads, loss, new_net_state = self._accum_loss_grads(
                self._policy.compute_copy(params), net_state, inputs,
                labels, feature_masks, label_masks, rng, accum_steps)
            new_params, new_updater = self._apply_updaters(
                params, updater_state, grads, iteration)
        return new_params, new_updater, new_net_state, loss, None

    @traced
    def _guarded_step_impl(self, params, updater_state, net_state,
                           iteration, lr_scale_host, inputs, labels,
                           feature_masks, label_masks, rng,
                           accum_steps: int):
        """Sentinel-checked optimizer step for the fused epoch program
        (see MultiLayerNetwork._guarded_step_impl): non-finite loss or
        gradients skip the updater apply via ``lax.cond`` (params/
        updater/net state carried unchanged) and raise the trip flag.
        Returns ``(params, updater, net_state, loss, tripped)``."""
        from deeplearning4j_tpu.resilience.guard import tree_all_finite

        with dtypes_mod.policy_scope(self._policy):
            fwd_params = self._policy.compute_copy(params)
            if accum_steps > 1:
                grads, loss, nst2 = self._accum_loss_grads(
                    fwd_params, net_state, inputs, labels, feature_masks,
                    label_masks, rng, accum_steps)
            else:
                (loss, (nst2, _)), grads = self._loss_grads(
                    fwd_params, net_state, inputs, labels, feature_masks,
                    label_masks, rng)
            # sentinel reads the f32 (master) grads post-upcast
            grads = self._policy.master_grads(grads)
            ok = jnp.isfinite(loss) & tree_all_finite(grads)

            def apply(_):
                p2, u2 = self._apply_updaters(
                    params, updater_state, grads, iteration,
                    lr_scale_host)
                return p2, u2, nst2

            def skip(_):
                return params, updater_state, net_state

            new_params, new_updater, new_nst = jax.lax.cond(
                ok, apply, skip, None)
        return new_params, new_updater, new_nst, loss, ~ok

    @traced
    def _telemetry_step_impl(self, params, updater_state, net_state,
                             iteration, lr_scale_host, inputs, labels,
                             feature_masks, label_masks, rng,
                             accum_steps: int, guard: bool,
                             metrics_stride: int):
        """Fused-path step with the in-program metrics pack (see
        MultiLayerNetwork._telemetry_step_impl): branch-for-branch the
        same math as the plain/accumulated/guarded step — the unguarded
        apply omits ``lr_scale_host`` exactly like ``_step_impl``, so
        telemetry-on params stay bitwise-identical to telemetry-off —
        plus the ``[4]`` f32 diagnostics vector. Returns ``(params,
        updater, net_state, loss, tripped-or-None, metrics)``."""
        from deeplearning4j_tpu.monitor.pack import step_metrics
        from deeplearning4j_tpu.resilience.guard import tree_all_finite

        with dtypes_mod.policy_scope(self._policy):
            fwd_params = self._policy.compute_copy(params)
            if accum_steps > 1:
                grads, loss, nst2 = self._accum_loss_grads(
                    fwd_params, net_state, inputs, labels, feature_masks,
                    label_masks, rng, accum_steps)
            else:
                (loss, (nst2, _)), grads = self._loss_grads(
                    fwd_params, net_state, inputs, labels, feature_masks,
                    label_masks, rng)
            # telemetry norms + sentinel read the f32 (master) grads
            grads = self._policy.master_grads(grads)
            if guard:
                ok = jnp.isfinite(loss) & tree_all_finite(grads)

                def apply(_):
                    p2, u2 = self._apply_updaters(
                        params, updater_state, grads, iteration,
                        lr_scale_host)
                    return p2, u2, nst2

                def skip(_):
                    return params, updater_state, net_state

                new_params, new_updater, new_nst = jax.lax.cond(
                    ok, apply, skip, None)
                tripped = ~ok
            else:
                new_params, new_updater = self._apply_updaters(
                    params, updater_state, grads, iteration)
                new_nst, tripped = nst2, None
            # report the scale actually APPLIED: the unguarded apply
            # omits lr_scale_host (bitwise parity with _step_impl), so
            # the lr_scale column must omit it too
            m = step_metrics(params, new_params, grads,
                             self._lr_scale(
                                 iteration,
                                 lr_scale_host if guard else None),
                             iteration, metrics_stride)
        return new_params, new_updater, new_nst, loss, tripped, m

    @functools.cached_property
    def _train_step(self):
        return jax.jit(self._step_impl, donate_argnums=(0, 1, 2))

    @functools.cached_property
    def _multi_train_step(self):
        """K optimizer steps fused into ONE XLA program via ``lax.scan``
        (the ComputationGraph counterpart of
        MultiLayerNetwork._multi_train_step): the batch transfers once and
        there is a single host dispatch per K steps."""

        def multi(params, updater_state, net_state, iteration0, inputs,
                  labels, feature_masks, label_masks, rngs, rnn_state):
            def body(carry, rng):
                params, upd, nst, rnn, it = carry
                p2, u2, s2, loss, rnn2 = self._step_impl(
                    params, upd, nst, it, inputs, labels, feature_masks,
                    label_masks, rng, rnn)
                return (p2, u2, s2, rnn2, it + 1), loss

            carry0 = (params, updater_state, net_state, rnn_state,
                      iteration0)
            (p, u, s, rnn, _), losses = jax.lax.scan(body, carry0, rngs)
            return p, u, s, losses[-1]

        return jax.jit(multi, donate_argnums=(0, 1, 2))

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
        (self.params, self.updater_state, self.net_state, loss) = (
            self._multi_train_step(
                self.params, self.updater_state, self.net_state,
                jnp.asarray(self.iteration_count, jnp.int32),
                tuple(jnp.asarray(f) for f in data.features),
                tuple(jnp.asarray(l) for l in data.labels),
                None if data.features_masks is None else tuple(
                    None if m is None else jnp.asarray(m)
                    for m in data.features_masks),
                None if data.labels_masks is None else tuple(
                    None if m is None else jnp.asarray(m)
                    for m in data.labels_masks),
                keys[1:], None,
            ))
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
    @traced
    def _epoch_run_fn(self, shuffle: bool, accum_steps: int = 1,
                      guard: bool = False, metrics_stride: int = 0):
        """The PURE chunk program: E epochs x N batches scanned over the
        HBM-resident ``[N, B, ...]`` stacks (tuples per input/output
        position); per-epoch device-side reshuffle via ``epoch_schedule``
        (the permutation runs over the unsharded batch-index axis — on a
        mesh the gathers stay shard-local). ``lr_scale_host`` is the host
        LR multiplier (a traced scalar — the halve_lr divergence policy
        adjusts it between chunks without recompiling); the unguarded
        step ignores it (it is 1.0 unless a guard policy changed it).
        ``guard=True`` routes each step through the numeric sentinel;
        ``metrics_stride > 0`` compiles the in-program metrics pack in.
        Outputs, in order: ``(params, updater, net_state, [E, N] hist[,
        [E, N] trips][, [E, N, 4] metrics])`` — trips iff guarded,
        metrics iff the pack is compiled in. Shared by the single-device
        jit and ``ParallelWrapper``'s SPMD jit."""

        def run(params, updater_state, net_state, iteration0,
                lr_scale_host, xs, ys, fms, lms, epoch_keys):
            n = xs[0].shape[0]

            def epoch_body(carry, ekey):
                params, upd, nst, it = carry
                order, step_keys = epoch_schedule(ekey, n, shuffle)

                def batch_body(c2, inp):
                    params, upd, nst, it = c2
                    i, rng = inp
                    batch = (tuple(x[i] for x in xs),
                             tuple(y[i] for y in ys),
                             None if fms is None
                             else tuple(m[i] for m in fms),
                             tuple(m[i] for m in lms), rng)
                    if metrics_stride:
                        p2, u2, s2, loss, tripped, m = (
                            self._telemetry_step_impl(
                                params, upd, nst, it, lr_scale_host,
                                *batch, accum_steps, guard,
                                metrics_stride))
                        out = (loss, tripped, m) if guard else (loss, m)
                        return (p2, u2, s2, it + 1), out
                    if guard:
                        p2, u2, s2, loss, tripped = self._guarded_step_impl(
                            params, upd, nst, it, lr_scale_host, *batch,
                            accum_steps)
                        return (p2, u2, s2, it + 1), (loss, tripped)
                    args = (params, upd, nst, it) + batch
                    if accum_steps > 1:
                        p2, u2, s2, loss, _ = self._accum_step_impl(
                            *args, accum_steps)
                    else:
                        p2, u2, s2, loss, _ = self._step_impl(*args, None)
                    return (p2, u2, s2, it + 1), loss

                (params, upd, nst, it), losses = jax.lax.scan(
                    batch_body, (params, upd, nst, it), (order, step_keys))
                return (params, upd, nst, it), losses

            carry0 = (params, updater_state, net_state, iteration0)
            (p, u, s, _), hist = jax.lax.scan(epoch_body, carry0, epoch_keys)
            if guard and metrics_stride:
                losses, trips, mets = hist
                return p, u, s, losses, trips, mets
            if guard:
                losses, trips = hist
                return p, u, s, losses, trips
            if metrics_stride:
                losses, mets = hist
                return p, u, s, losses, mets
            return p, u, s, hist

        return run

    def _epoch_train_step(self, shuffle: bool, accum_steps: int = 1,
                          guard: bool = False, metrics_stride: int = 0):
        """Jitted fused epoch program (one entry per (shuffle, accum,
        guard, metrics_stride)); params/updater/net state donated,
        dataset stacks resident. Entries are :class:`ProfiledProgram`s —
        pass-through with ``DL4J_PROFILE`` off, cost/memory-profiled
        once per signature with it on (monitor/profile.py)."""
        from deeplearning4j_tpu.monitor.profile import ProfiledProgram

        key = (shuffle, accum_steps, guard, metrics_stride)
        fn = self._epoch_steps.get(key)
        if fn is None:
            fn = ProfiledProgram(
                jax.jit(self._epoch_run_fn(shuffle, accum_steps, guard,
                                           metrics_stride),
                        donate_argnums=(0, 1, 2)),
                name="ComputationGraph", key=key)
            self._epoch_steps[key] = fn
        return fn

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
        from deeplearning4j_tpu.compile_cache import ensure_compile_cache
        from deeplearning4j_tpu.resilience.guard import nan_guard_policy

        ensure_compile_cache()
        self._ensure_init()
        if num_epochs <= 0:
            return None
        if accum_steps is None:
            accum_steps = accum_steps_default()
        if not self.fused_epochs_supported():
            if isinstance(data, DeviceMultiDataSetCache):
                raise ValueError(
                    "this configuration needs the per-step fit loop "
                    "(TBPTT / iterations > 1) — pass the original "
                    "iterator, not a DeviceMultiDataSetCache")
            for _ in range(num_epochs):
                self.fit(data)
            return None
        cache = data if isinstance(data, DeviceMultiDataSetCache) else (
            DeviceMultiDataSetCache.build(data, budget_mb=cache_mb,
                                          mesh=mesh,
                                          accum_steps=accum_steps))
        if cache is None:
            stream_epochs(self, data, num_epochs)
            return None
        accum = effective_accum_steps(accum_steps, cache.batch)
        if cache.mesh is not None:
            self._place_on_mesh(cache.mesh)
        guard = nan_guard_policy() if guard is None else guard
        guarded = guard != "off"
        stride = fused_metrics_stride(telemetry)

        def launch(epoch_keys):
            # resolved per launch: a topology reshard clears the program
            # cache (see MultiLayerNetwork.fit_epochs)
            step = self._epoch_train_step(shuffle, accum, guarded, stride)
            out = step(
                self.params, self.updater_state, self.net_state,
                jnp.asarray(self.iteration_count, jnp.int32),
                jnp.asarray(self._lr_scale_host, jnp.float32),
                cache.features, cache.labels, cache.features_masks,
                cache.labels_masks, epoch_keys)
            (self.params, self.updater_state, self.net_state) = out[:3]
            hist = out[3]
            trips = out[4] if guarded else None
            mets = out[-1] if stride else None
            return hist, trips, mets

        def replay_step(params, upd, nst, it, i, rng):
            # per-step replay for DL4J_NAN_GUARD=raise localization —
            # accumulation split included, matching the fused run's
            # per-microbatch rng stream
            args = (params, upd, nst, jnp.asarray(it, jnp.int32),
                    tuple(x[i] for x in cache.features),
                    tuple(y[i] for y in cache.labels),
                    None if cache.features_masks is None
                    else tuple(m[i] for m in cache.features_masks),
                    tuple(m[i] for m in cache.labels_masks), rng)
            if accum > 1:
                p, u, s, loss, _ = self._accum_step_impl(*args, accum)
            else:
                p, u, s, loss, _ = self._train_step(*args, None)
            return p, u, s, loss

        return drive_epoch_chunks(self, cache, num_epochs, chunk_epochs,
                                  launch, shuffle=shuffle, guard=guard,
                                  replay_step=replay_step,
                                  on_chunk=on_chunk,
                                  reshard=lambda m: elastic_reshard(
                                      self, cache, m))

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
        inputs = tuple(jnp.asarray(f) for f in mds.features)
        labels = tuple(jnp.asarray(l) for l in mds.labels)
        fms = (None if mds.features_masks is None else tuple(
            None if m is None else jnp.asarray(m) for m in mds.features_masks))
        lms = (None if mds.labels_masks is None else tuple(
            None if m is None else jnp.asarray(m) for m in mds.labels_masks))
        (self.params, self.updater_state, self.net_state, loss,
         new_rnn) = self._train_step(
            self.params, self.updater_state, self.net_state,
            jnp.asarray(self.iteration_count, jnp.int32),
            inputs, labels, fms, lms, rng, rnn_state)
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
        """Fused TBPTT over the DAG: ``lax.scan`` over full windows in ONE
        XLA program, rnn carry threaded with stop-gradient truncation at
        boundaries (see MultiLayerNetwork._tbptt_train_step; reference
        walks windows host-side — ComputationGraph.java:489-534).
        Temporal ([b, t, ...]) arrays and [b, t] masks are windowed; static
        inputs (e.g. an image conditioning a caption LSTM) are closed over
        whole and reused every window."""
        window = self.conf.tbptt_fwd_length

        def tbptt(params, updater_state, net_state, iteration0, inputs,
                  labels, fms, lms, rngs, rnn_state0):
            t = max(f.shape[1] for f in inputs if f.ndim == 3)
            n_win = t // window

            def to_windows(a, temporal):
                if a is None or not temporal:
                    return None
                b = a.shape[0]
                shaped = a.reshape((b, n_win, window) + a.shape[2:])
                return jnp.moveaxis(shaped, 1, 0)

            in_w = tuple(to_windows(f, f.ndim == 3) for f in inputs)
            lb_w = tuple(to_windows(l, l.ndim == 3) for l in labels)
            fm_w = (None if fms is None
                    else tuple(to_windows(m, True) for m in fms))
            lm_w = (None if lms is None
                    else tuple(to_windows(m, True) for m in lms))

            def pick(windowed, whole):
                return tuple(
                    w if w is not None else s
                    for w, s in zip(windowed, whole))

            def body(carry, inp):
                params, upd, nst, rnn, it = carry
                iw, lw, fw, lmw, rng = inp
                p2, u2, nst2, loss, rnn2 = self._step_impl(
                    params, upd, nst, it, pick(iw, inputs),
                    pick(lw, labels),
                    None if fw is None else pick(fw, fms),
                    None if lmw is None else pick(lmw, lms),
                    rng, rnn)
                rnn2 = jax.tree_util.tree_map(jax.lax.stop_gradient, rnn2)
                return (p2, u2, nst2, rnn2, it + 1), loss

            carry0 = (params, updater_state, net_state, rnn_state0,
                      iteration0)
            (p, u, s, rnn, _), losses = jax.lax.scan(
                body, carry0, (in_w, lb_w, fm_w, lm_w, rngs))
            return p, u, s, rnn, losses[-1]

        return jax.jit(tbptt, donate_argnums=(0, 1, 2))

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
                self.params, self.updater_state, self.net_state,
                jnp.asarray(self.iteration_count, jnp.int32),
                tuple(jnp.asarray(f) for f in head.features),
                tuple(jnp.asarray(l) for l in head.labels),
                None if head.features_masks is None else tuple(
                    None if m is None else jnp.asarray(m)
                    for m in head.features_masks),
                None if head.labels_masks is None else tuple(
                    None if m is None else jnp.asarray(m)
                    for m in head.labels_masks),
                keys[1:], rnn_state)
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
