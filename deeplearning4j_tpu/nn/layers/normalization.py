"""Batch normalization + local response normalization.

Reference: nn/layers/normalization/BatchNormalization.java (batch statistics
at :146-147, γ/β scale-shift, ``lockGammaBeta`` :85, running-mean decay for
inference) and LocalResponseNormalization.java (cross-channel LRN à la
AlexNet). Running statistics live in the layer *state* pytree, threaded
through the jitted train step functionally instead of mutated in place.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.dtypes import get_policy
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.base import LayerImpl, register_layer_impl


@register_layer_impl(L.BatchNormalization)
class BatchNormImpl(LayerImpl):
    """Normalises over all axes except the last (features for 2-D [b,f],
    channels for NHWC 4-D), matching the reference's per-feature/per-channel
    statistics."""

    kind = "dsl.norm"

    def init_params(self, key):
        conf = self.conf
        policy = get_policy()
        n = conf.n_out if conf.n_out is not None else conf.n_in
        if n is None:
            raise ValueError("BatchNormalization needs n_in (set_input_type or explicit)")
        if conf.lock_gamma_beta:
            return {}
        return {
            "gamma": jnp.full((n,), conf.gamma, policy.param_dtype),
            "beta": jnp.full((n,), conf.beta, policy.param_dtype),
        }

    def init_state(self):
        conf = self.conf
        n = conf.n_out if conf.n_out is not None else conf.n_in
        return {
            "mean": jnp.zeros((n,), jnp.float32),
            "var": jnp.ones((n,), jnp.float32),
        }

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        conf = self.conf
        axes = tuple(range(x.ndim - 1))
        if train:
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
            decay = conf.decay
            new_state = {
                "mean": decay * state["mean"] + (1.0 - decay) * mean,
                "var": decay * state["var"] + (1.0 - decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        xhat = (x - mean) * lax.rsqrt(var + conf.eps)
        if conf.lock_gamma_beta:
            y = conf.gamma * xhat + conf.beta
        else:
            y = params["gamma"] * xhat + params["beta"]
        return self.activation_fn()(y), new_state


@register_layer_impl(L.LocalResponseNormalization)
class LRNImpl(LayerImpl):
    """Cross-channel LRN on NHWC: y = x / (k + α·Σ_{j∈window} x_j²)^β."""

    kind = "dsl.norm"

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        conf = self.conf
        half = conf.n // 2
        sq = x * x
        # sum over a window of `n` adjacent channels (last axis)
        window = (1,) * (x.ndim - 1) + (conf.n,)
        pads = ((0, 0),) * (x.ndim - 1) + ((half, conf.n - 1 - half),)
        ssum = lax.reduce_window(sq, 0.0, lax.add, window, (1,) * x.ndim, pads)
        denom = (conf.k + conf.alpha * ssum) ** conf.beta
        return self.activation_fn()(x / denom), state
