"""Plain reference for the CIFAR-form ResNet: inference forward, and the first
optimizer steps of training.

Straightforward ``jax.numpy`` / ``lax.conv_general_dilated`` in float32 under
``jax.default_matmul_precision("highest")``; nothing imported from the
program. It reads the program's parameter and running-statistics trees as data,
by the layer names the configuration implies (``stem``, ``stem_bn``,
``s<stage>b<block>_{c1,b1,c2,b2,proj}``, ``out``), and follows He et al.:

    x = relu(bn(conv3x3(image)))
    per block:  y = relu(bn(conv3x3(x, stride)));  y = bn(conv3x3(y))
                x = relu(y + (conv1x1(x, stride) if shape changes else x))
    p = softmax(mean_hw(x) W + b)

Convolutions pad "same" as XLA does (the odd pixel goes after) and carry a bias
(the DSL's ConvolutionLayer has one). Inference batch norm uses the running
mean and variance with the layer's epsilon 1e-5; training batch norm uses the
batch's own mean and biased variance. Training is mean cross-entropy, its
gradient by ``jax.grad`` of this file's forward, and Adam as the reference
framework writes it: ``lr * mhat / (sqrt(vhat) + eps)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
BN_EPS = 1e-5


def _conv(x, p, stride):
    w = p["W"].astype(F32)                       # [kh, kw, c_in, c_out]
    pads = []
    for size, k in zip(x.shape[1:3], w.shape[:2]):
        out = -(-size // stride)
        total = max((out - 1) * stride + k - size, 0)
        pads.append((total // 2, total - total // 2))
    y = lax.conv_general_dilated(x, w, (stride, stride), pads,
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"].astype(F32)


def _bn(x, p, mean, var):
    xhat = (x - mean) / jnp.sqrt(var + BN_EPS)
    return p["gamma"].astype(F32) * xhat + p["beta"].astype(F32)


def _logits(params, x, cfg, stats):
    """``stats(name, y) -> (mean, var)`` per channel for the batch norm
    layer ``name`` applied to ``y``."""
    def bn(name, y):
        return _bn(y, params[name], *stats(name, y))

    x = jax.nn.relu(bn("stem_bn", _conv(x, params["stem"], 1)))
    c_prev = cfg["stem_channels"]
    for s, c in enumerate(cfg["stage_channels"]):
        for b in range(cfg["blocks_per_stage"]):
            n = f"s{s}b{b}"
            stride = 2 if (s > 0 and b == 0) else 1
            y = jax.nn.relu(bn(n + "_b1", _conv(x, params[n + "_c1"], stride)))
            y = bn(n + "_b2", _conv(y, params[n + "_c2"], 1))
            if stride != 1 or c_prev != c:
                x = _conv(x, params[n + "_proj"], stride)
            x = jax.nn.relu(y + x)
            c_prev = c
    pooled = jnp.mean(x, axis=(1, 2))
    return (pooled @ params["out"]["W"].astype(F32)
            + params["out"]["b"].astype(F32))


def probabilities(params, state, images, cfg):
    """images [B, H, W, C] -> class probabilities [B, classes], float32,
    batch norm on the running statistics in ``state``."""
    def f(params, state, x):
        def running(name, _y):
            return state[name]["mean"].astype(F32), state[name]["var"].astype(F32)

        return jax.nn.softmax(_logits(params, x, cfg, running), axis=-1)

    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(params, state, jnp.asarray(images, F32))


def first_losses(params, batches, cfg, *, micro, lr, beta1=0.9, beta2=0.999,
                 eps=1e-6):
    """The training loss of each of the first ``len(batches)`` optimizer
    steps from ``params``: ``batches`` is a list of ``(images [B, H, W, C],
    one-hot labels [B, classes])``. A batch goes through as ``B / micro``
    micro-batches whose losses and gradients are averaged, so that float32
    activations of a large batch fit one chip; batch norm then takes its
    statistics from ``micro`` samples rather than ``B``, which is the one
    place this differs from a whole-batch step."""
    def loss(p, x, y):
        def batch_stats(_name, a):
            return jnp.mean(a, axis=(0, 1, 2)), jnp.var(a, axis=(0, 1, 2))

        logp = jax.nn.log_softmax(_logits(p, x, cfg, batch_stats), axis=-1)
        return -jnp.mean(jnp.sum(y * logp, axis=-1))

    def step(carry, x, y, t):
        p, m, v = carry
        k = x.shape[0] // micro
        xs = x.reshape((k, micro) + x.shape[1:])
        ys = y.reshape((k, micro) + y.shape[1:])

        def body(acc, xy):
            l, g = jax.value_and_grad(loss)(p, *xy)
            return (acc[0] + l / k,
                    jax.tree_util.tree_map(lambda a, b: a + b / k, acc[1], g)
                    ), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, p)
        (l, g), _ = lax.scan(body, (jnp.zeros((), F32), zero), (xs, ys))
        tmap = jax.tree_util.tree_map
        m = tmap(lambda a, b: beta1 * a + (1 - beta1) * b, m, g)
        v = tmap(lambda a, b: beta2 * a + (1 - beta2) * b * b, v, g)
        p = tmap(lambda w, a, b: w - lr * (a / (1 - beta1 ** t))
                 / (jnp.sqrt(b / (1 - beta2 ** t)) + eps), p, m, v)
        return (p, m, v), l

    with jax.default_matmul_precision("highest"):
        step = jax.jit(step)
        p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), params)
        zero = jax.tree_util.tree_map(jnp.zeros_like, p)
        carry, losses = (p, zero, zero), []
        for t, (x, y) in enumerate(batches, start=1):
            carry, l = step(carry, jnp.asarray(x, F32), jnp.asarray(y, F32),
                            jnp.asarray(t, F32))
            losses.append(float(l))
    return losses
