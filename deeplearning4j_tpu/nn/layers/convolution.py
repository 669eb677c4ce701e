"""Convolution + pooling layers, NHWC, direct XLA convolution.

The reference lowers conv to im2col + GEMM on ND4J
(nn/layers/convolution/ConvolutionLayer.java:109,135) and pooling to
im2col-based reductions (subsampling/SubsamplingLayer.java:117-147). On TPU
the idiomatic lowering is ``lax.conv_general_dilated`` (XLA maps it straight
onto the MXU with fused padding) and ``lax.reduce_window`` for pooling — no
materialised im2col buffer, which is strictly less HBM traffic.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.dtypes import get_policy
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.enums import PoolingType
from deeplearning4j_tpu.nn.layers.base import LayerImpl, register_layer_impl
from deeplearning4j_tpu.ops.initializers import conv_fans, init_weights

_DIMSPEC = ("NHWC", "HWIO", "NHWC")


@register_layer_impl(L.ConvolutionLayer)
class ConvolutionImpl(LayerImpl):
    kind = "dsl.conv"

    def init_params(self, key):
        conf = self.conf
        kh, kw = conf.kernel_size
        policy = get_policy()
        kshape = (kh, kw, conf.n_in, conf.n_out)
        fan_in, fan_out = conv_fans(kshape)
        W = init_weights(
            key, kshape, conf.weight_init.value,
            fan_in=fan_in, fan_out=fan_out,
            distribution=conf.dist, dtype=policy.param_dtype,
        )
        b = jnp.full((conf.n_out,), conf.bias_init, policy.param_dtype)
        return {"W": W, "b": b}

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        conf = self.conf
        x = self.maybe_dropout(x, train=train, rng=rng)
        policy = get_policy()
        if conf.convolution_mode == "same":
            padding = "SAME"
        else:
            ph, pw = conf.padding
            padding = [(ph, ph), (pw, pw)]
        y = lax.conv_general_dilated(
            policy.cast_compute(x),
            policy.cast_compute(params["W"]),
            window_strides=tuple(conf.stride),
            padding=padding,
            dimension_numbers=_DIMSPEC,
        )
        y = policy.cast_output(y) + params["b"]
        return self.activation_fn()(y), state


@register_layer_impl(L.GlobalPoolingLayer)
class GlobalPoolingImpl(LayerImpl):
    """Mean/max/sum/pnorm over spatial axes (NHWC [b,h,w,c] → [b,c]) or the
    time axis (RNN [b,t,f] → [b,f]); honors the feature mask for
    variable-length series (masked steps excluded from the statistic)."""

    kind = "dsl.pool"

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        conf = self.conf
        if x.ndim == 4:
            axes = (1, 2)
            m = None
        elif x.ndim == 3:
            axes = (1,)
            m = None if mask is None else mask[..., None].astype(x.dtype)
        else:
            raise ValueError(f"GlobalPooling expects rank 3/4 input, got {x.ndim}")
        pt = conf.pooling_type
        if pt == PoolingType.MAX:
            if m is not None:
                x = jnp.where(m > 0, x, -jnp.inf)
            y = jnp.max(x, axis=axes)
            if m is not None:
                # all-padding examples (mask row entirely 0) yield -inf;
                # emit 0 instead so the loss/grads stay finite
                any_valid = jnp.max(m, axis=axes) > 0
                y = jnp.where(any_valid, y, 0.0)
        elif pt == PoolingType.SUM:
            if m is not None:
                x = x * m
            y = jnp.sum(x, axis=axes)
        elif pt == PoolingType.AVG:
            if m is not None:
                y = jnp.sum(x * m, axis=axes) / jnp.maximum(
                    jnp.sum(m, axis=axes), 1.0)
            else:
                y = jnp.mean(x, axis=axes)
        elif pt == PoolingType.PNORM:
            p = float(conf.pnorm)
            if m is not None:
                x = x * m
            y = jnp.sum(jnp.abs(x) ** p, axis=axes) ** (1.0 / p)
        else:
            raise ValueError(f"unknown pooling type {pt}")
        return self.activation_fn()(y), state


@register_layer_impl(L.SubsamplingLayer)
class SubsamplingImpl(LayerImpl):
    kind = "dsl.pool"

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        conf = self.conf
        kh, kw = conf.kernel_size
        sh, sw = conf.stride
        ph, pw = conf.padding
        window = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        pads = ((0, 0), (ph, ph), (pw, pw), (0, 0))
        pt = conf.pooling_type
        if pt == PoolingType.MAX:
            y = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)
        elif pt == PoolingType.SUM:
            y = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        elif pt == PoolingType.AVG:
            y = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
            y = y / float(kh * kw)
        elif pt == PoolingType.PNORM:
            p = float(conf.pnorm)
            y = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides, pads)
            y = y ** (1.0 / p)
        else:
            raise ValueError(f"unknown pooling type {pt}")
        return self.activation_fn()(y), state
