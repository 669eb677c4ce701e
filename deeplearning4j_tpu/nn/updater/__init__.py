"""Updaters (per-param gradient transforms), LR policies, gradient normalization.

Functional re-implementation of nn/updater/BaseUpdater.java:34 (preApply
gradient normalization :126, per-param GradientUpdater dispatch, minibatch
division) and the nd4j learning package (AdaGrad/Adam/AdaDelta/Nesterovs/
RmsProp/Sgd/NoOp), plus nn/conf/LearningRatePolicy schedules.

Updater state is an explicit pytree mirroring the params (one slot per param
array), which makes it (a) serializable into checkpoints — the reference's
``updater.bin`` contract (util/ModelSerializer.java) — and (b) aggregatable
across data-parallel replicas the way Spark param-averaging merges updater
state (nn/updater/aggregate/UpdaterAggregator).

L1/L2 are NOT added here: they are folded into the loss (so ``jax.grad``
produces the regularized gradient and the score includes the penalty, matching
BaseOptimizer's score = loss + calcL1 + calcL2).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.enums import (
    GradientNormalization,
    LearningRatePolicy,
    Updater,
)
from deeplearning4j_tpu.nn.conf.layers import LayerConf

# ---------------------------------------------------------------------------
# Hyperparameters (resolved per layer from conf + defaults)
# ---------------------------------------------------------------------------

_DEFAULTS = {
    "momentum": 0.9,
    "rho": 0.95,
    "epsilon": 1e-6,
    "rms_decay": 0.95,
    "adam_mean_decay": 0.9,
    "adam_var_decay": 0.999,
}


@dataclasses.dataclass(frozen=True)
class UpdaterSpec:
    """Static (trace-time) updater description for one layer."""

    kind: Updater = Updater.SGD
    learning_rate: float = 0.1
    bias_learning_rate: Optional[float] = None
    momentum: float = 0.9
    rho: float = 0.95
    epsilon: float = 1e-6
    rms_decay: float = 0.95
    adam_mean_decay: float = 0.9
    adam_var_decay: float = 0.999
    # ((iteration, momentum), ...) sorted — sticky from each key on
    # (BaseUpdater.java:75-80 applyMomentumDecayPolicy); a tuple (not a
    # dict) so the frozen spec stays hashable for jit static args
    momentum_schedule: Optional[Tuple[Tuple[int, float], ...]] = None
    gradient_normalization: GradientNormalization = GradientNormalization.NONE
    gradient_normalization_threshold: float = 1.0

    @staticmethod
    def from_layer_conf(conf: LayerConf, default_lr: float,
                        momentum_schedule: Optional[Dict[int, float]] = None
                        ) -> "UpdaterSpec":
        def pick(name):
            v = getattr(conf, name, None)
            return _DEFAULTS[name] if v is None else float(v)

        sched = None
        if momentum_schedule:
            sched = tuple(sorted(
                (int(k), float(v)) for k, v in momentum_schedule.items()))
        return UpdaterSpec(
            momentum_schedule=sched,
            kind=conf.updater or Updater.SGD,
            learning_rate=(
                float(conf.learning_rate)
                if conf.learning_rate is not None
                else float(default_lr)
            ),
            bias_learning_rate=(
                float(conf.bias_learning_rate)
                if conf.bias_learning_rate is not None
                else None
            ),
            momentum=pick("momentum"),
            rho=pick("rho"),
            epsilon=pick("epsilon"),
            rms_decay=pick("rms_decay"),
            adam_mean_decay=pick("adam_mean_decay"),
            adam_var_decay=pick("adam_var_decay"),
            gradient_normalization=(
                conf.gradient_normalization or GradientNormalization.NONE
            ),
            gradient_normalization_threshold=float(
                conf.gradient_normalization_threshold
            ),
        )


# ---------------------------------------------------------------------------
# State init
# ---------------------------------------------------------------------------


def init_updater_state(spec: UpdaterSpec, params: Any) -> Any:
    """Mirror pytree of per-param state for this layer's updater kind."""
    zeros = lambda p: jnp.zeros_like(p)
    if spec.kind in (Updater.SGD, Updater.NONE):
        return jax.tree_util.tree_map(lambda p: jnp.zeros((0,), p.dtype), params)
    if spec.kind in (Updater.ADAGRAD, Updater.RMSPROP):
        return jax.tree_util.tree_map(zeros, params)
    if spec.kind == Updater.NESTEROVS:
        return jax.tree_util.tree_map(zeros, params)
    if spec.kind == Updater.ADADELTA:
        return jax.tree_util.tree_map(
            lambda p: {"msg": jnp.zeros_like(p), "msdx": jnp.zeros_like(p)}, params
        )
    if spec.kind == Updater.ADAM:
        return jax.tree_util.tree_map(
            lambda p: {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}, params
        )
    raise ValueError(f"unsupported updater {spec.kind}")


# ---------------------------------------------------------------------------
# Gradient normalization (BaseUpdater.preApply :126)
# ---------------------------------------------------------------------------


def normalize_gradients(spec: UpdaterSpec, grads: Any) -> Any:
    gn = spec.gradient_normalization
    thr = spec.gradient_normalization_threshold
    if gn == GradientNormalization.NONE:
        return grads
    leaves = jax.tree_util.tree_leaves(grads)
    if gn == GradientNormalization.RENORMALIZE_L2_PER_LAYER:
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves) + 1e-12)
        return jax.tree_util.tree_map(lambda g: g / norm, grads)
    if gn == GradientNormalization.RENORMALIZE_L2_PER_PARAM_TYPE:
        return jax.tree_util.tree_map(
            lambda g: g / (jnp.linalg.norm(g.ravel()) + 1e-12), grads
        )
    if gn == GradientNormalization.CLIP_ELEMENTWISE_ABSOLUTE_VALUE:
        return jax.tree_util.tree_map(lambda g: jnp.clip(g, -thr, thr), grads)
    if gn == GradientNormalization.CLIP_L2_PER_LAYER:
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves) + 1e-12)
        scale = jnp.minimum(1.0, thr / norm)
        return jax.tree_util.tree_map(lambda g: g * scale, grads)
    if gn == GradientNormalization.CLIP_L2_PER_PARAM_TYPE:
        def clip(g):
            norm = jnp.linalg.norm(g.ravel()) + 1e-12
            return g * jnp.minimum(1.0, thr / norm)

        return jax.tree_util.tree_map(clip, grads)
    raise ValueError(gn)


# ---------------------------------------------------------------------------
# Per-param updater math
# ---------------------------------------------------------------------------


def _piecewise_constant(schedule: Dict[int, float], it, default):
    """Sticky piecewise-constant lookup shared by the momentum schedule
    and the SCHEDULE lr policy: value of the latest key ≤ ``it`` (traced
    scalar), else ``default``."""
    boundaries = jnp.asarray(sorted(schedule), jnp.float32)
    values = jnp.asarray([schedule[k] for k in sorted(schedule)],
                         jnp.float32)
    idx = jnp.sum(boundaries <= it) - 1
    return jnp.where(idx < 0, default, values[jnp.maximum(idx, 0)])


def _apply_one(spec: UpdaterSpec, lr, g, s, t):
    """Returns (step_to_subtract, new_state) for one param array."""
    kind = spec.kind
    if kind == Updater.SGD:
        return lr * g, s
    if kind == Updater.NONE:
        return g, s
    if kind == Updater.ADAGRAD:
        s2 = s + g * g
        return lr * g / (jnp.sqrt(s2) + spec.epsilon), s2
    if kind == Updater.RMSPROP:
        s2 = spec.rms_decay * s + (1.0 - spec.rms_decay) * g * g
        return lr * g / (jnp.sqrt(s2) + spec.epsilon), s2
    if kind == Updater.NESTEROVS:
        # nd4j Nesterovs: v' = mu*v - lr*g; step = -(mu*v' - lr*g) ⇒
        # params += mu*v' - lr*g (we return the value to SUBTRACT)
        mu = spec.momentum
        if spec.momentum_schedule:
            # sticky switch: the latest key ≤ the 0-based iteration wins
            mu = _piecewise_constant(
                dict(spec.momentum_schedule), t - 1.0, default=mu)
        v_new = mu * s - lr * g
        step = -(mu * v_new - lr * g)
        return step, v_new
    if kind == Updater.ADADELTA:
        rho = spec.rho
        msg = rho * s["msg"] + (1.0 - rho) * g * g
        dx = jnp.sqrt((s["msdx"] + spec.epsilon) / (msg + spec.epsilon)) * g
        msdx = rho * s["msdx"] + (1.0 - rho) * dx * dx
        return dx, {"msg": msg, "msdx": msdx}
    if kind == Updater.ADAM:
        b1, b2 = spec.adam_mean_decay, spec.adam_var_decay
        m = b1 * s["m"] + (1.0 - b1) * g
        v = b2 * s["v"] + (1.0 - b2) * g * g
        mhat = m / (1.0 - b1 ** t)
        vhat = v / (1.0 - b2 ** t)
        return lr * mhat / (jnp.sqrt(vhat) + spec.epsilon), {"m": m, "v": v}
    raise ValueError(kind)


def apply_updater(
    spec: UpdaterSpec,
    grads: Dict[str, Any],
    state: Dict[str, Any],
    lr_scale: jnp.ndarray,
    step_count: jnp.ndarray,
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Transform one layer's gradients into parameter steps.

    ``lr_scale`` multiplies the spec's base lr (LR-policy factor, traced);
    ``step_count`` is the 1-based global step for Adam bias correction.
    Returns (steps, new_state) with steps to be SUBTRACTED from params.
    """
    from deeplearning4j_tpu.nn.layers.base import is_bias_param

    grads = normalize_gradients(spec, grads)
    t = jnp.maximum(step_count, 1).astype(jnp.float32)

    def walk(sub_g, sub_s):
        steps, new_state = {}, {}
        for name in sub_g:
            if isinstance(sub_g[name], dict):  # nested (e.g. biLSTM fwd/bwd)
                steps[name], new_state[name] = walk(sub_g[name], sub_s[name])
                continue
            lr = spec.learning_rate
            if spec.bias_learning_rate is not None and is_bias_param(name):
                lr = spec.bias_learning_rate
            lr = lr * lr_scale
            steps[name], new_state[name] = _apply_one(spec, lr, sub_g[name], sub_s[name], t)
        return steps, new_state

    return walk(grads, state)


# ---------------------------------------------------------------------------
# Flattened (grouped) updater apply — the fused optimizer tail
# ---------------------------------------------------------------------------


def flat_apply_safe(live_params) -> bool:
    """True when the live parameter leaves all carry the SAME placement,
    so the flattened (concat) updater sweep keeps that placement.

    A ravel→concat→slice chain over leaves with HETEROGENEOUS shardings
    (P(None,'model'), P('model'), P()) is exact on jax 0.9.0 — the wrong
    values jax 0.4.37's GSPMD returned for it are gone — but the
    partitioner can only build the concat by replicating every leaf
    first (XLA logs "Involuntary full rematerialization" for it): each
    chip would gather the full parameter set every step, which is the
    memory tensor-parallel and FSDP placements exist to avoid. Such
    state takes the per-layer apply instead. The decision is made at
    TRACE time from the network's live (concrete) params — consistent
    with the traced call because jit re-traces whenever input shardings
    change."""
    shardings = set()
    for leaf in jax.tree_util.tree_leaves(live_params):
        s = getattr(leaf, "sharding", None)
        if s is None:
            return False  # tracer/host array: no placement info → safe path
        try:
            shardings.add(s)
        except TypeError:  # unhashable sharding object
            return False
        if len(shardings) > 1:
            return False
    return True


def per_layer_apply_updaters(items, params, updater_state, grads,
                             lr_scale, step_count):
    """The classic per-layer loop (one :func:`apply_updater` per layer)
    — the placement-preserving form of :func:`grouped_apply_updaters`
    for state whose leaves are sharded differently (see
    :func:`flat_apply_safe`), factored out of both network classes.
    Same math, L unrolled copies."""
    new_params, new_updater = {}, {}
    for key, spec in items:
        steps_i, upd_i = apply_updater(
            spec, grads[key], updater_state[key], lr_scale, step_count)
        new_params[key] = jax.tree_util.tree_map(
            lambda p, s: p - s.astype(p.dtype), params[key], steps_i)
        new_updater[key] = upd_i
    return new_params, new_updater


_FLAT_UNIT = 1024  # the TPU's one-dimensional tile, T(1024)


def _cat_flat(leaves):
    """Concatenate arrays as one flat vector (identity-ish for one) whose
    length is a multiple of ``_FLAT_UNIT``: a zeros tail is the
    concatenation's last operand when the leaves' sizes do not add up to
    one.

    The tail is there for the TPU compiler's layout. ResNet-18's
    11,176,970 elements (= 2 · 5 · 1,117,697) it factored as
    ``f32[1117697,10]`` under an (8, 128) tile — ten of every 128 lanes
    hold data, 546 MiB a copy for 42.6 MiB, a 12.8-fold padding every
    elementwise op of the sweep then moved: 11.16 ms of a 164.8 ms step
    on a v5e, 1.27 ms with the tail (PERF.md §6, PR 50). A length of
    whole tiles keeps the dense ``T(1024)`` form. The tail is zeros in
    the gradient and in every state vector alike, so the step there is
    ``0 / (√0 + ε) = 0``; the split walks offsets from 0 and never reads
    it."""
    flats = [l.reshape(-1) for l in leaves]
    if len(flats) == 1:
        return flats[0]
    tail = -sum(f.size for f in flats) % _FLAT_UNIT
    if tail:
        flats.append(jnp.zeros((tail,), flats[0].dtype))
    return jnp.concatenate(flats)


def _iter_leaf_records(grads, state, params, path=()):
    """Yield ``(path, g, s, p)`` per param leaf of one layer's subtree.
    ``s`` is that leaf's updater-state slot: an array (SGD/AdaGrad/
    RMSProp/Nesterovs) or a dict of arrays (Adam/AdaDelta)."""
    for name in sorted(grads):
        g = grads[name]
        if isinstance(g, dict):  # nested (e.g. biLSTM fwd/bwd)
            yield from _iter_leaf_records(g, state[name], params[name],
                                          path + (name,))
        else:
            yield path + (name,), g, state[name], params[name]


def grouped_apply_updaters(items, params, updater_state, grads, lr_scale,
                           step_count):
    """The whole multi-layer optimizer tail as ONE flattened sweep.

    ``items`` is the ordered ``(layer_key, spec)`` list; ``params`` /
    ``updater_state`` / ``grads`` are the per-layer-keyed pytrees. Param
    leaves are grouped by ``(spec, effective lr, dtype)``, each group's
    leaves raveled into ONE flat vector, and :func:`_apply_one` runs once
    per group — so the traced updater math (the Adam/Nesterovs/... op
    chain XLA must schedule) is per-GROUP, not per-leaf: depth-invariant
    for the common one-updater network instead of L unrolled copies. The
    per-leaf residue is only trivial reshape/slice data movement that XLA
    fuses into the surrounding program.

    Exactly the math of the per-layer :func:`apply_updater` loop: the
    updater ops are elementwise, so concat → op → split is bitwise the
    per-leaf op, and per-layer gradient NORMALIZATION (whose norms are
    defined over one layer's gradient) still runs per layer before
    grouping. ``bias_learning_rate`` leaves split into their own group.

    Every flat vector of a group (gradient, each state slot) ends in the
    same zeros tail up to a multiple of 1,024 elements (:func:`_cat_flat`):
    the TPU compiler keeps such a vector in its dense one-dimensional
    layout, where a length like 11,176,970 became ``[1117697, 10]`` under
    an (8, 128) tile and every op of the sweep moved 12.8 times its data.
    The tail's step is 0, is finite for the NaN guard, and the split below
    never reaches it.

    Returns ``(new_params, new_updater_state)`` with the input pytree
    structure (donation-compatible round-trip).
    """
    from deeplearning4j_tpu.nn.layers.base import is_bias_param

    t = jnp.maximum(step_count, 1).astype(jnp.float32)
    groups: Dict[Any, list] = {}
    order = []
    new_params: Dict[str, Any] = {}
    new_updater: Dict[str, Any] = {}
    for key, spec in items:
        # structure skeletons so empty layers round-trip too
        new_params[key] = _skeleton(params[key])
        new_updater[key] = _skeleton(updater_state[key])
        g_layer = grads[key]
        if spec.gradient_normalization != GradientNormalization.NONE:
            # norms are per-LAYER by definition — normalize before the
            # cross-layer grouping so semantics match the per-layer loop
            g_layer = normalize_gradients(spec, g_layer)
        for path, g, s, p in _iter_leaf_records(
                g_layer, updater_state[key], params[key]):
            lr = spec.learning_rate
            if (spec.bias_learning_rate is not None
                    and is_bias_param(path[-1])):
                lr = spec.bias_learning_rate
            gk = (spec, lr, str(g.dtype))
            if gk not in groups:
                groups[gk] = []
                order.append(gk)
            groups[gk].append((key, path, g, s, p))

    for gk in order:
        spec, lr, _ = gk
        recs = groups[gk]
        flat_g = _cat_flat([g for _, _, g, _, _ in recs])
        s0 = recs[0][3]
        if isinstance(s0, dict):
            flat_s = {k2: _cat_flat([s[k2] for _, _, _, s, _ in recs])
                      for k2 in sorted(s0)}
        else:
            flat_s = _cat_flat([s for _, _, _, s, _ in recs])
        step_flat, s2_flat = _apply_one(spec, lr * lr_scale, flat_g,
                                        flat_s, t)
        off = 0
        state_offs = ({k2: 0 for k2 in sorted(s0)}
                      if isinstance(s0, dict) else 0)
        for key, path, g, s, p in recs:
            size = int(g.size)
            leaf_step = step_flat[off:off + size].reshape(g.shape)
            off += size
            _put(new_params[key], path, p - leaf_step.astype(p.dtype))
            if isinstance(s, dict):
                slot = {}
                for k2 in sorted(s):
                    ssz = int(s[k2].size)
                    so = state_offs[k2]
                    slot[k2] = s2_flat[k2][so:so + ssz].reshape(
                        s[k2].shape)
                    state_offs[k2] = so + ssz
            else:
                ssz = int(s.size)
                slot = s2_flat[state_offs:state_offs + ssz].reshape(
                    s.shape)
                state_offs += ssz
            _put(new_updater[key], path, slot)
    return new_params, new_updater


def _skeleton(tree):
    if isinstance(tree, dict):
        return {k: _skeleton(v) for k, v in tree.items()}
    return tree  # leaf placeholder, overwritten by _put


def _put(root, path, value):
    node = root
    for part in path[:-1]:
        node = node[part]
    node[path[-1]] = value


# ---------------------------------------------------------------------------
# Learning-rate policies (nn/conf/LearningRatePolicy)
# ---------------------------------------------------------------------------


def lr_policy_scale(
    policy: LearningRatePolicy,
    iteration: jnp.ndarray,
    decay_rate: float,
    steps: float,
    power: float,
    schedule: Optional[Dict[int, float]] = None,
    base_lr: float = 1.0,
) -> jnp.ndarray:
    """Multiplicative factor on the base lr at ``iteration`` (traced scalar)."""
    it = iteration.astype(jnp.float32)
    if policy == LearningRatePolicy.NONE:
        return jnp.asarray(1.0)
    if policy == LearningRatePolicy.EXPONENTIAL:
        return jnp.power(decay_rate, it)
    if policy == LearningRatePolicy.INVERSE:
        return jnp.power(1.0 + decay_rate * it, -power)
    if policy == LearningRatePolicy.POLY:
        return jnp.power(jnp.maximum(0.0, 1.0 - it / jnp.maximum(steps, 1.0)), power)
    if policy == LearningRatePolicy.SIGMOID:
        return 1.0 / (1.0 + jnp.exp(-decay_rate * (it - steps)))
    if policy == LearningRatePolicy.STEP:
        return jnp.power(decay_rate, jnp.floor(it / jnp.maximum(steps, 1.0)))
    if policy == LearningRatePolicy.TORCH_STEP:
        return jnp.power(decay_rate, jnp.floor(it / jnp.maximum(steps, 1.0)))
    if policy == LearningRatePolicy.SCHEDULE:
        if not schedule:
            return jnp.asarray(1.0)
        # piecewise-constant absolute lr: factor = schedule_lr / base_lr
        factors = {k: v / max(base_lr, 1e-30)
                   for k, v in schedule.items()}
        return _piecewise_constant(factors, it, default=1.0)
    if policy == LearningRatePolicy.SCORE:
        # score-based decay is driven host-side (Solver watches the score and
        # shrinks lr); inside the step it is identity.
        return jnp.asarray(1.0)
    raise ValueError(policy)
