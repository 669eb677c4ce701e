"""Layer implementation protocol + registry.

Contract (functional equivalent of nn/api/Layer.java:37):

- ``init_params(key) -> params`` — named param table for this layer, the
  pytree analogue of the reference's ``Map<String, INDArray>`` param table
  ("W"/"b" keys, DefaultParamInitializer).
- ``init_state() -> state`` — non-trainable state (batchnorm running stats,
  RNN carry for ``rnn_time_step``); empty dict for stateless layers.
- ``forward(params, x, state, *, train, rng, mask) -> (y, new_state)`` —
  pure; under ``jit`` the whole network's forwards fuse into one XLA program.

Dropout on the layer *input* (the reference's per-layer ``dropOut`` applies to
input activations, BaseLayer/Dropout semantics) is handled here in
``maybe_dropout`` with an explicit PRNG key.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers import LayerConf
from deeplearning4j_tpu.nn.conf.preprocessors import apply_preprocessor
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.scopes import layer_scope

Params = Dict[str, jnp.ndarray]
State = Dict[str, jnp.ndarray]

# exact leaf names treated as biases (unregularized; bias_learning_rate)
_BIAS_PARAM_NAMES = frozenset({"b", "vb", "hb", "be", "bd", "beta", "bias"})


def is_bias_param(name: str) -> bool:
    return name in _BIAS_PARAM_NAMES

_IMPL_REGISTRY: Dict[Type[LayerConf], Type["LayerImpl"]] = {}


def register_layer_impl(conf_cls: Type[LayerConf]):
    def deco(impl_cls):
        _IMPL_REGISTRY[conf_cls] = impl_cls
        return impl_cls

    return deco


def get_layer_impl(conf: LayerConf) -> "LayerImpl":
    impl_cls = _IMPL_REGISTRY.get(type(conf))
    if impl_cls is None:
        # fall back to closest registered base class (e.g. RnnOutputLayer
        # subclasses OutputLayer)
        for cls in type(conf).__mro__:
            if cls in _IMPL_REGISTRY:
                impl_cls = _IMPL_REGISTRY[cls]
                break
    if impl_cls is None:
        raise ValueError(f"no implementation registered for {type(conf).__name__}")
    return impl_cls(conf)


def forward_layer(impl: "LayerImpl", name, params, x, state, *, pre=None,
                  batch=None, train: bool, rng, mask):
    """One layer of a network as both network classes run it: its input
    preprocessor, its share of the key, ``impl.forward`` -- all under the
    scope of the layer's kind and, inside it, of the user's ``name`` for it
    (``scopes.layer_scope``), so every op a layer lowers to carries both in
    a device trace, forward and backward. ``mask`` reaches a layer whose
    input is a series only. Returns ``(y, new_state, rng)`` with ``rng``
    advanced."""
    with layer_scope(impl.kind, name):
        if pre is not None:
            x, rng = apply_preprocessor(pre, x, batch=batch, rng=rng)
        sub_rng = None
        if rng is not None:
            rng, sub_rng = jax.random.split(rng)
        y, new_state = impl.forward(
            params, x, state, train=train, rng=sub_rng,
            mask=mask if x.ndim == 3 else None)
    return y, new_state, rng


class LayerImpl:
    # the layer's scope in a lowered program: a ``dsl.*`` name of
    # ``deeplearning4j_tpu/scopes.py``, by kind of layer
    kind = "dsl.layer"

    def __init__(self, conf: LayerConf):
        self.conf = conf

    # ---- params ----
    def init_params(self, key: jax.Array) -> Params:
        return {}

    def init_state(self) -> State:
        return {}

    def num_params(self) -> int:
        key = jax.random.PRNGKey(0)
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(self.init_params(key)))

    # ---- forward ----
    def forward(
        self,
        params: Params,
        x: jnp.ndarray,
        state: State,
        *,
        train: bool = False,
        rng: Optional[jax.Array] = None,
        mask: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, State]:
        raise NotImplementedError

    # ---- helpers ----
    def activation_fn(self):
        return get_activation(self.conf.activation)

    def maybe_dropout(
        self, x: jnp.ndarray, *, train: bool, rng: Optional[jax.Array]
    ) -> jnp.ndarray:
        p = float(self.conf.dropout or 0.0)
        if not train or p <= 0.0:
            return x
        if rng is None:
            raise ValueError(
                f"layer {self.conf.name or type(self.conf).__name__} has dropout "
                "but no rng key was provided to forward(train=True)"
            )
        keep = 1.0 - p
        mask = jax.random.bernoulli(rng, keep, x.shape)
        # inverted dropout (scale at train time), matching nd4j Dropout
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)

    def l1_l2_penalty(self, params: Params) -> jnp.ndarray:
        """L1/L2 regularization on weight params (not biases), as in
        BaseUpdater.postApply / BaseLayer.calcL1/calcL2. Recurses into
        nested param trees (e.g. bidirectional LSTM fwd/bwd subtrees)."""
        l1 = float(self.conf.l1 or 0.0)
        l2 = float(self.conf.l2 or 0.0)
        if l1 == 0.0 and l2 == 0.0:
            return jnp.asarray(0.0)

        def walk(tree):
            total = jnp.asarray(0.0)
            for name, p in tree.items():
                if isinstance(p, dict):
                    total = total + walk(p)
                    continue
                if is_bias_param(name):  # biases unregularized
                    continue
                if l1:
                    total = total + l1 * jnp.sum(jnp.abs(p))
                if l2:
                    total = total + 0.5 * l2 * jnp.sum(p * p)
            return total

        return walk(params)
