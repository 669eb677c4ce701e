"""Recurrent layers: Graves LSTM (peepholes), bidirectional LSTM, GRU, LSTM.

Reference: nn/layers/recurrent/GravesLSTM.java + LSTMHelpers.java:45 (gate
math :159-194; per-timestep accumulation GEMMs :297-300),
GravesBidirectionalLSTM.java, GRU.java, BaseRecurrentLayer.java (stateMap for
``rnnTimeStep``).

TPU-first design: the input projection for ALL timesteps is hoisted into one
large GEMM ([b·t, n_in] @ [n_in, 4n] — MXU-friendly), and only the recurrence
([b, n] @ [n, 4n] per step) runs inside ``lax.scan``. This replaces the
reference's per-timestep Java loop issuing two GEMMs per step. Gradients
through the scan come from ``jax.grad`` (XLA differentiates the scan),
replacing LSTMHelpers.backpropGradientHelper.

Masking (variable-length series): at masked steps the carry is held and the
output zeroed, matching the reference's mask semantics
(TestVariableLengthTS) so padded steps influence nothing.

Time layout: [batch, time, features].
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.dtypes import get_policy
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.base import LayerImpl, Params, State, register_layer_impl
from deeplearning4j_tpu.ops.initializers import init_weights


def _lstm_params(key, n_in, n, conf, peepholes: bool) -> Params:
    policy = get_policy()
    k1, k2, k3 = jax.random.split(key, 3)
    W = init_weights(k1, (n_in, 4 * n), conf.weight_init.value,
                     fan_in=n_in, fan_out=n, distribution=conf.dist,
                     dtype=policy.param_dtype)
    RW = init_weights(k2, (n, 4 * n), conf.weight_init.value,
                      fan_in=n, fan_out=n, distribution=conf.dist,
                      dtype=policy.param_dtype)
    # gate order [i, f, o, g]; forget-gate bias init (reference
    # GravesLSTMParamInitializer sets forget bias to 1)
    b = jnp.zeros((4 * n,), policy.param_dtype)
    b = b.at[n:2 * n].set(conf.forget_gate_bias_init)
    params = {"W": W, "RW": RW, "b": b}
    if peepholes:
        params["pI"] = jnp.zeros((n,), policy.param_dtype)
        params["pF"] = jnp.zeros((n,), policy.param_dtype)
        params["pO"] = jnp.zeros((n,), policy.param_dtype)
    return params


def _lstm_scan(params, x, act, *, peepholes: bool, mask=None, h0=None, c0=None,
               reverse: bool = False):
    """Run the LSTM over [b, t, n_in]; returns ([b, t, n], (h_T, c_T))."""
    policy = get_policy()
    b, t, _ = x.shape
    n = params["RW"].shape[0]
    # one big input GEMM over all timesteps
    xW = policy.cast_compute(x).reshape(b * t, -1) @ policy.cast_compute(params["W"])
    xW = policy.cast_output(xW).reshape(b, t, 4 * n) + params["b"]
    xW_t = jnp.swapaxes(xW, 0, 1)  # [t, b, 4n] scan layout
    if mask is not None:
        mask_t = jnp.swapaxes(mask.astype(xW.dtype), 0, 1)[..., None]  # [t, b, 1]
    else:
        mask_t = jnp.ones((t, 1, 1), xW.dtype)
    h = jnp.zeros((b, n), xW.dtype) if h0 is None else h0
    c = jnp.zeros((b, n), xW.dtype) if c0 is None else c0
    RW = policy.cast_compute(params["RW"])
    pI = params.get("pI")
    pF = params.get("pF")
    pO = params.get("pO")

    def step(carry, inp):
        h_prev, c_prev = carry
        z, m = inp
        z = z + policy.cast_output(policy.cast_compute(h_prev) @ RW)
        zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
        if peepholes:
            zi = zi + pI * c_prev
            zf = zf + pF * c_prev
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf)
        g = act(zg)
        c_new = f * c_prev + i * g
        if peepholes:
            zo = zo + pO * c_new
        o = jax.nn.sigmoid(zo)
        h_new = o * act(c_new)
        # hold carry at masked steps; zero the emitted output
        h_new = m * h_new + (1.0 - m) * h_prev
        c_new = m * c_new + (1.0 - m) * c_prev
        return (h_new, c_new), h_new * m

    (hT, cT), ys = lax.scan(step, (h, c), (xW_t, jnp.broadcast_to(mask_t, (t, b, 1))),
                            reverse=reverse)
    return jnp.swapaxes(ys, 0, 1), (hT, cT)


@register_layer_impl(L.GravesLSTM)
class GravesLSTMImpl(LayerImpl):
    kind = "dsl.recurrent"
    peepholes = True

    def init_params(self, key):
        return _lstm_params(key, self.conf.n_in, self.conf.n_out, self.conf,
                            self.peepholes)

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        h0 = state.get("h")
        c0 = state.get("c")
        ys, (hT, cT) = _lstm_scan(params, x, self.activation_fn(),
                                  peepholes=self.peepholes, mask=mask,
                                  h0=h0, c0=c0)
        new_state = dict(state)
        if "h" in state:  # stateful mode (rnn_time_step) — thread the carry
            new_state["h"] = hT
            new_state["c"] = cT
        return ys, new_state


@register_layer_impl(L.LSTM)
class LSTMImpl(GravesLSTMImpl):
    peepholes = False


@register_layer_impl(L.GravesBidirectionalLSTM)
class BiLSTMImpl(LayerImpl):
    """Forward + backward Graves LSTM, outputs summed (the reference's ADD
    combination, GravesBidirectionalLSTM.java)."""

    kind = "dsl.recurrent"

    def init_params(self, key):
        kf, kb = jax.random.split(key)
        conf = self.conf
        return {
            "fwd": _lstm_params(kf, conf.n_in, conf.n_out, conf, True),
            "bwd": _lstm_params(kb, conf.n_in, conf.n_out, conf, True),
        }

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        act = self.activation_fn()
        yf, _ = _lstm_scan(params["fwd"], x, act, peepholes=True, mask=mask)
        yb, _ = _lstm_scan(params["bwd"], x, act, peepholes=True, mask=mask,
                           reverse=True)
        return yf + yb, state


@register_layer_impl(L.GRU)
class GRUImpl(LayerImpl):
    kind = "dsl.recurrent"

    def init_params(self, key):
        conf = self.conf
        policy = get_policy()
        n_in, n = conf.n_in, conf.n_out
        k1, k2 = jax.random.split(key)
        W = init_weights(k1, (n_in, 3 * n), conf.weight_init.value,
                         fan_in=n_in, fan_out=n, distribution=conf.dist,
                         dtype=policy.param_dtype)
        RW = init_weights(k2, (n, 3 * n), conf.weight_init.value,
                          fan_in=n, fan_out=n, distribution=conf.dist,
                          dtype=policy.param_dtype)
        b = jnp.zeros((3 * n,), policy.param_dtype)
        return {"W": W, "RW": RW, "b": b}

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        policy = get_policy()
        act = self.activation_fn()
        b, t, _ = x.shape
        n = self.conf.n_out
        xW = policy.cast_compute(x).reshape(b * t, -1) @ policy.cast_compute(params["W"])
        xW = policy.cast_output(xW).reshape(b, t, 3 * n) + params["b"]
        xW_t = jnp.swapaxes(xW, 0, 1)
        if mask is not None:
            mask_t = jnp.broadcast_to(
                jnp.swapaxes(mask.astype(xW.dtype), 0, 1)[..., None], (t, b, 1))
        else:
            mask_t = jnp.ones((t, b, 1), xW.dtype)
        RW = policy.cast_compute(params["RW"])
        Rr, Ru, Rc = RW[:, :n], RW[:, n:2 * n], RW[:, 2 * n:]
        h = state.get("h")
        if h is None:
            h = jnp.zeros((b, n), xW.dtype)

        def step(h_prev, inp):
            z, m = inp
            zr, zu, zc = jnp.split(z, 3, axis=-1)
            hc = policy.cast_compute(h_prev)
            r = jax.nn.sigmoid(zr + policy.cast_output(hc @ Rr))
            u = jax.nn.sigmoid(zu + policy.cast_output(hc @ Ru))
            cand = act(zc + policy.cast_output(policy.cast_compute(r * h_prev) @ Rc))
            h_new = u * h_prev + (1.0 - u) * cand
            h_new = m * h_new + (1.0 - m) * h_prev
            return h_new, h_new * m

        hT, ys = lax.scan(step, h, (xW_t, mask_t))
        new_state = dict(state)
        if "h" in state:
            new_state["h"] = hT
        return jnp.swapaxes(ys, 0, 1), new_state


@register_layer_impl(L.ImageLSTM)
class ImageLSTMImpl(LayerImpl):
    """Image-captioning LSTM (ImageLSTM.java:54, "based on Karpathy et al.").

    Params follow the reference's ImageLSTMParamInitializer: ``RW``
    ([n_in + hidden, 4·hidden] combined input+recurrent gate weights, the
    reference's RECURRENT_WEIGHT_KEY at :58), ``W`` ([hidden, n_out] output
    projection), ``b`` ([n_out]). Forward runs the gate recurrence as a
    ``lax.scan`` and projects every step to the output space; decoding is a
    host-driven beam search (the reference's BeamSearch inner class :282)
    around a jitted single-step cell.
    """

    kind = "dsl.recurrent"

    def _hidden(self) -> int:
        return self.conf.hidden_size or self.conf.n_out

    def init_params(self, key):
        conf = self.conf
        policy = get_policy()
        n_in, hid, n_out = conf.n_in, self._hidden(), conf.n_out
        k1, k2 = jax.random.split(key)
        RW = init_weights(k1, (n_in + hid, 4 * hid), conf.weight_init.value,
                          fan_in=n_in + hid, fan_out=hid,
                          distribution=conf.dist, dtype=policy.param_dtype)
        W = init_weights(k2, (hid, n_out), conf.weight_init.value,
                         distribution=conf.dist, dtype=policy.param_dtype)
        gate_bias = jnp.zeros((4 * hid,), policy.param_dtype)
        gate_bias = gate_bias.at[hid:2 * hid].set(conf.forget_gate_bias_init)
        return {"RW": RW, "gb": gate_bias,
                "W": W, "b": jnp.zeros((n_out,), policy.param_dtype)}

    def _gates(self, z, c, act):
        hid = self._hidden()
        i = jax.nn.sigmoid(z[:, :hid])
        f = jax.nn.sigmoid(z[:, hid:2 * hid])
        o = jax.nn.sigmoid(z[:, 2 * hid:3 * hid])
        g = act(z[:, 3 * hid:])
        c_new = f * c + i * g
        h_new = o * act(c_new)
        return h_new, c_new

    def _cell(self, params, x_t, h, c):
        """One gate step (beam-search decoding): x_t [b, n_in],
        h/c [b, hid] → (h', c')."""
        z = jnp.concatenate([x_t, h], axis=-1) @ params["RW"] + params["gb"]
        return self._gates(z, c, self.activation_fn())

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        n_in = self.conf.n_in
        # the combined RW param splits into _lstm_scan's input/recurrent
        # halves — one shared implementation of the hoisted-GEMM recurrence
        view = {"W": params["RW"][:n_in], "RW": params["RW"][n_in:],
                "b": params["gb"]}
        hs, (hT, cT) = _lstm_scan(view, x, self.activation_fn(),
                                  peepholes=False, mask=mask,
                                  h0=state.get("h"), c0=state.get("c"))
        ys = hs @ params["W"] + params["b"]
        if mask is not None:  # zero padded steps after the bias add
            ys = ys * mask.astype(ys.dtype)[..., None]
        new_state = dict(state)
        if "h" in state:
            new_state["h"] = hT
            new_state["c"] = cT
        return ys, new_state

    # -- decoding (BeamSearch, ImageLSTM.java:282) ----------------------
    def beam_search(self, params, xi, word_vectors, n_steps: int = 20,
                    beam_width: int = 3, end_token: Optional[int] = None):
        """Decode token sequences conditioned on image representation ``xi``.

        ``xi``: [n_in] image embedding consumed as step 0;
        ``word_vectors``: [n_out, n_in] input vector per output token (the
        reference's ``ws``). Returns [(tokens, log_prob)] sorted best-first.

        Decodes THIS layer's output projection — train with a parameterless
        head (``LossLayer(activation="softmax")``) so the decoded
        distribution is exactly the trained one; under further
        parameterized layers, decode from the full network instead.
        """
        if not hasattr(self, "_jit_cell"):
            self._jit_cell = jax.jit(
                lambda p, x_t, h, c: self._cell(p, x_t, h, c))
        hid = self._hidden()
        h = jnp.zeros((1, hid))
        c = jnp.zeros((1, hid))
        h, c = self._jit_cell(params, jnp.asarray(xi)[None, :], h, c)
        beams = [(0.0, [], h, c)]
        ws = jnp.asarray(word_vectors)
        done = []
        for _ in range(n_steps):
            candidates = []
            for logp, toks, h, c in beams:
                logprobs = np.asarray(jax.nn.log_softmax(
                    h @ params["W"] + params["b"])[0])
                for tok in np.argsort(-logprobs)[:beam_width]:
                    candidates.append((logp + float(logprobs[tok]),
                                       toks + [int(tok)], h, c))
            candidates.sort(key=lambda b: -b[0])
            beams = []
            for logp, toks, h, c in candidates[:beam_width]:
                if end_token is not None and toks[-1] == end_token:
                    done.append((toks, logp))
                    continue
                h2, c2 = self._jit_cell(params, ws[toks[-1]][None, :], h, c)
                beams.append((logp, toks, h2, c2))
            if not beams:
                break
        done.extend((toks, logp) for logp, toks, _, _ in beams)
        return sorted(done, key=lambda p: -p[1])
