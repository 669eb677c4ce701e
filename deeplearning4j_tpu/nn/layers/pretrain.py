"""Pretraining layers: denoising AutoEncoder and RBM (contrastive divergence).

Reference: nn/layers/feedforward/autoencoder/AutoEncoder.java and
nn/layers/feedforward/rbm/RBM.java:68 (contrastiveDivergence :101,
sampleHiddenGivenVisible :225, propUp/propDown :226,284).

The functional-PRNG treatment of CD-k (SURVEY "hard parts" #2): Gibbs chains
consume explicit jax PRNG keys split per step, so pretraining remains
deterministic per seed and jit-compilable (the k-step chain is a
``lax.scan``). The CD update is not the gradient of a tractable loss, so RBM
exposes ``pretrain_grads`` directly rather than a loss for ``jax.grad``;
AutoEncoder exposes ``pretrain_loss`` which IS differentiated.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.dtypes import get_policy
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.enums import HiddenUnit, VisibleUnit
from deeplearning4j_tpu.nn.layers.base import LayerImpl, Params, register_layer_impl
from deeplearning4j_tpu.ops.initializers import init_weights
from deeplearning4j_tpu.ops.losses import compute_loss


@register_layer_impl(L.AutoEncoder)
class AutoEncoderImpl(LayerImpl):
    """Encoder y = act(xW + b); decoder z = act(yWᵀ + vb) (tied weights, as
    in the reference's params W, b, vb from PretrainParamInitializer)."""

    kind = "dsl.dense"

    def init_params(self, key):
        conf = self.conf
        policy = get_policy()
        W = init_weights(key, (conf.n_in, conf.n_out), conf.weight_init.value,
                         distribution=conf.dist, dtype=policy.param_dtype)
        return {
            "W": W,
            "b": jnp.full((conf.n_out,), conf.bias_init, policy.param_dtype),
            "vb": jnp.zeros((conf.n_in,), policy.param_dtype),
        }

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        act = self.activation_fn()
        return act(x @ params["W"] + params["b"]), state

    def decode(self, params, y):
        act = self.activation_fn()
        return act(y @ params["W"].T + params["vb"])

    def pretrain_loss(self, params, x, rng: jax.Array):
        """Denoising reconstruction loss: corrupt → encode → decode → xent."""
        conf = self.conf
        if conf.corruption_level > 0:
            keep = jax.random.bernoulli(rng, 1.0 - conf.corruption_level, x.shape)
            xc = jnp.where(keep, x, 0.0)
        else:
            xc = x
        act = self.activation_fn()
        y = act(xc @ params["W"] + params["b"])
        z = self.decode(params, y)
        return compute_loss(conf.loss_function, z, x)


@register_layer_impl(L.RecursiveAutoEncoder)
class RecursiveAutoEncoderImpl(LayerImpl):
    """Recursive autoencoder (RecursiveAutoEncoder.java, 162 LoC).

    Folds a sequence left-to-right from a zero root: p₀ = 0;
    pᵢ = act(W_e·[pᵢ₋₁; xᵢ] + b_e), with per-fold reconstruction
    [p̂; x̂] = act(W_d·pᵢ + b_d) scored against [pᵢ₋₁; xᵢ] under the layer's
    ``loss_function``. The fold is a ``lax.scan``; forward returns the root
    encoding. Masked timesteps (variable-length series) hold the carry and
    contribute no reconstruction loss. Rank-2 inputs are length-1 sequences.
    """

    kind = "dsl.dense"

    def init_params(self, key):
        conf = self.conf
        policy = get_policy()
        d_in, d = conf.n_in, conf.n_out
        k_e, k_d = jax.random.split(key)
        return {
            "We": init_weights(k_e, (d + d_in, d), conf.weight_init.value,
                               distribution=conf.dist,
                               dtype=policy.param_dtype),
            "be": jnp.full((d,), conf.bias_init, policy.param_dtype),
            "Wd": init_weights(k_d, (d, d + d_in), conf.weight_init.value,
                               distribution=conf.dist,
                               dtype=policy.param_dtype),
            "bd": jnp.zeros((d + d_in,), policy.param_dtype),
        }

    def _fold(self, params, x, mask=None):
        """x: (batch, time, n_in), mask: (batch, time) or None →
        (root (batch, n_out), mean per-step recon loss over unmasked steps)."""
        act = self.activation_fn()
        d = self.conf.n_out
        batch, t = x.shape[0], x.shape[1]
        p0 = jnp.zeros((batch, d), x.dtype)
        if mask is None:
            mask_t = jnp.ones((t, batch), x.dtype)
        else:
            mask_t = jnp.swapaxes(mask.astype(x.dtype), 0, 1)

        def step(p, inputs):
            xt, mt = inputs
            cc = jnp.concatenate([p, xt], axis=-1)
            p_new = act(cc @ params["We"] + params["be"])
            recon = act(p_new @ params["Wd"] + params["bd"])
            p_next = jnp.where(mt[:, None] > 0, p_new, p)  # hold at masked
            return p_next, (recon, cc)

        root, (recons, ccs) = lax.scan(step, p0, (jnp.swapaxes(x, 0, 1),
                                                  mask_t))
        feat = recons.shape[-1]
        return root, compute_loss(
            self.conf.loss_function, recons.reshape(t * batch, feat),
            ccs.reshape(t * batch, feat), mask=mask_t.reshape(t * batch))

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        if x.ndim == 2:
            x = x[:, None, :]
            mask = None
        root, _ = self._fold(params, x, mask=mask)
        return root, state

    def pretrain_loss(self, params, x, rng: jax.Array, mask=None):
        if x.ndim == 2:
            x = x[:, None, :]
            mask = None
        _, err = self._fold(params, x, mask=mask)
        return err


@register_layer_impl(L.RBM)
class RBMImpl(LayerImpl):
    kind = "dsl.dense"

    def init_params(self, key):
        conf = self.conf
        policy = get_policy()
        W = init_weights(key, (conf.n_in, conf.n_out), conf.weight_init.value,
                         distribution=conf.dist, dtype=policy.param_dtype)
        return {
            "W": W,
            "hb": jnp.zeros((conf.n_out,), policy.param_dtype),
            "vb": jnp.zeros((conf.n_in,), policy.param_dtype),
        }

    # propUp (RBM.java:226)
    def prop_up(self, params, v):
        pre = v @ params["W"] + params["hb"]
        return self._hidden_activation(pre)

    # propDown (RBM.java:284)
    def prop_down(self, params, h):
        pre = h @ params["W"].T + params["vb"]
        return self._visible_activation(pre)

    def _hidden_activation(self, pre):
        hu = self.conf.hidden_unit
        if hu == HiddenUnit.BINARY:
            return jax.nn.sigmoid(pre)
        if hu == HiddenUnit.RECTIFIED:
            return jax.nn.relu(pre)
        if hu == HiddenUnit.GAUSSIAN:
            return pre
        if hu == HiddenUnit.SOFTMAX:
            return jax.nn.softmax(pre, axis=-1)
        raise ValueError(hu)

    def _visible_activation(self, pre):
        vu = self.conf.visible_unit
        if vu == VisibleUnit.BINARY:
            return jax.nn.sigmoid(pre)
        if vu in (VisibleUnit.GAUSSIAN, VisibleUnit.LINEAR):
            return pre
        if vu == VisibleUnit.SOFTMAX:
            return jax.nn.softmax(pre, axis=-1)
        raise ValueError(vu)

    def _sample_hidden(self, params, v, key):
        mean = self.prop_up(params, v)
        if self.conf.hidden_unit == HiddenUnit.BINARY:
            return mean, jax.random.bernoulli(key, mean).astype(mean.dtype)
        if self.conf.hidden_unit == HiddenUnit.GAUSSIAN:
            return mean, mean + jax.random.normal(key, mean.shape, mean.dtype)
        return mean, mean  # rectified/softmax: mean-field

    def _sample_visible(self, params, h, key):
        mean = self.prop_down(params, h)
        if self.conf.visible_unit == VisibleUnit.BINARY:
            return mean, jax.random.bernoulli(key, mean).astype(mean.dtype)
        if self.conf.visible_unit == VisibleUnit.GAUSSIAN:
            return mean, mean + jax.random.normal(key, mean.shape, mean.dtype)
        return mean, mean

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        return self.prop_up(params, x), state

    def pretrain_grads(self, params, v0, rng: jax.Array) -> Tuple[Params, jnp.ndarray]:
        """CD-k gradients (RBM.java contrastiveDivergence :101) + recon error.

        Returns (grads, score): grads follow the convention 'descend on
        grads', i.e. grads = -(positive_phase - negative_phase)/batch.
        """
        k = max(1, int(self.conf.k))
        batch = v0.shape[0]
        h0_mean = self.prop_up(params, v0)
        key0, keys = rng, jax.random.split(rng, 2 * k + 1)
        _, h_sample = self._sample_hidden(params, v0, keys[0])

        def gibbs(carry, ks):
            h_s, _ = carry
            kv, kh = ks
            v_mean, v_s = self._sample_visible(params, h_s, kv)
            h_mean, h_s2 = self._sample_hidden(params, v_s, kh)
            return (h_s2, (v_mean, v_s, h_mean)), None

        carry = (h_sample, (v0, v0, h0_mean))
        step_keys = keys[1:2 * k + 1].reshape(k, 2, -1)
        (h_last, (vk_mean, vk_sample, hk_mean)), _ = lax.scan(gibbs, carry, step_keys)

        inv_b = 1.0 / float(batch)
        gW = -(v0.T @ h0_mean - vk_sample.T @ hk_mean) * inv_b
        ghb = -jnp.mean(h0_mean - hk_mean, axis=0)
        gvb = -jnp.mean(v0 - vk_sample, axis=0)
        score = jnp.mean(jnp.sum((v0 - vk_mean) ** 2, axis=-1))
        return {"W": gW, "hb": ghb, "vb": gvb}, score

    # API parity with the reference's pretrain path
    def pretrain_loss(self, params, x, rng):
        _, score = self.pretrain_grads(params, x, rng)
        return score

    def free_energy(self, params, v):
        """F(v) = -vb·v - Σ softplus(vW + hb) (binary units)."""
        wx_b = v @ params["W"] + params["hb"]
        return -v @ params["vb"] - jnp.sum(jax.nn.softplus(wx_b), axis=-1)
