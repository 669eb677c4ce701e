"""Dense / output / embedding / activation / dropout / loss layers.

Reference counterparts: nn/layers/feedforward/dense/DenseLayer.java,
nn/layers/BaseOutputLayer.java, feedforward/embedding/EmbeddingLayer.java,
nn/layers/ActivationLayer.java. Forward math matches BaseLayer.preOutput
(z = x·W + b) with the activation from the registry; the embedding layer is a
gather (``jnp.take``) rather than the reference's one-hot matmul — same
result, MXU-free and HBM-cheap.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.dtypes import get_policy
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.base import LayerImpl, Params, State, register_layer_impl
from deeplearning4j_tpu.ops.initializers import init_weights


@register_layer_impl(L.DenseLayer)
class DenseImpl(LayerImpl):
    kind = "dsl.dense"

    def init_params(self, key):
        conf = self.conf
        wkey, _ = jax.random.split(key)
        policy = get_policy()
        W = init_weights(
            wkey,
            (conf.n_in, conf.n_out),
            conf.weight_init.value,
            distribution=conf.dist,
            dtype=policy.param_dtype,
        )
        b = jnp.full((conf.n_out,), conf.bias_init, policy.param_dtype)
        return {"W": W, "b": b}

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        policy = get_policy()
        z = policy.cast_compute(x) @ policy.cast_compute(params["W"])
        z = policy.cast_output(z) + params["b"]
        return self.activation_fn()(z), state


@register_layer_impl(L.OutputLayer)
class OutputImpl(DenseImpl):
    """Dense + activation; the loss itself is applied by the network using
    ``conf.loss_function`` (BaseOutputLayer computes loss against labels)."""


@register_layer_impl(L.RnnOutputLayer)
class RnnOutputImpl(DenseImpl):
    """Per-timestep dense: [b, t, f] · W — XLA batches the time axis into one
    GEMM (reference reshapes to 2-D, RnnOutputLayer.java)."""


@register_layer_impl(L.EmbeddingLayer)
class EmbeddingImpl(LayerImpl):
    kind = "dsl.embed"

    def init_params(self, key):
        conf = self.conf
        policy = get_policy()
        W = init_weights(
            key,
            (conf.n_in, conf.n_out),
            conf.weight_init.value,
            distribution=conf.dist,
            dtype=policy.param_dtype,
        )
        b = jnp.full((conf.n_out,), conf.bias_init, policy.param_dtype)
        return {"W": W, "b": b}

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        # x: integer indices [b] or [b, 1] or one-hot [b, n_in]
        if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim >= 2 and x.shape[-1] == self.conf.n_in:
            idx = jnp.argmax(x, axis=-1)
        else:
            idx = x.astype(jnp.int32)
            if idx.ndim >= 2 and idx.shape[-1] == 1:
                idx = idx[..., 0]
        out = jnp.take(params["W"], idx, axis=0) + params["b"]
        return self.activation_fn()(out), state


@register_layer_impl(L.ActivationLayer)
class ActivationImpl(LayerImpl):
    kind = "dsl.act"

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        return self.activation_fn()(x), state


@register_layer_impl(L.DropoutLayer)
class DropoutImpl(LayerImpl):
    kind = "dsl.act"

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        return self.maybe_dropout(x, train=train, rng=rng), state


@register_layer_impl(L.LossLayer)
class LossLayerImpl(LayerImpl):
    kind = "dsl.act"

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        return self.activation_fn()(x), state
