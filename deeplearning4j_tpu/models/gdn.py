"""Gated DeltaNet (arXiv:2412.06464; the linear-attention mixer of
``qwen3_next``), as ``TransformerLM._block`` runs it.

Per value head, with a recurrent state ``S`` [dk, dv] in float32, zero at a
request's start (``x`` is the block's normed input; ``Hk`` key heads, ``Hv``
value heads, key head j serving value heads ``j Hv/Hk .. (j + 1) Hv/Hk - 1``):

    [q~ ; k~ ; v~ ; z] = x W_qkvz       D -> 2 Hk dk + 2 Hv dv
    [b ; a]            = x W_ba         D -> 2 Hv
    [q ; k ; v] = silu(conv([q~ ; k~ ; v~]))    depthwise, causal, no bias
    q, k    = q / ||q||, k / ||k||  per head;  q = q dk^-1/2
    beta_t  = sigmoid(b_t)                              one a value head
    g_t     = -exp(A_log) softplus(a_t + dt_bias)       one a value head
    S'      = exp(g_t) S_{t-1}
    S_t     = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t     = S_t^T q_t
    y       = concat_h(rmsnorm_w(o_t) silu(z_t,h)) W_o

It is ``models/kda.py``'s delta rule with ``diag(exp(g_t))`` a scalar, and it
runs on that module's two forms (``kda_scan``, ``kda_step``: ``g`` with a
last axis of 1), its convolution, its convolution tail and its rule for a row
that holds no token. What is this mixer's own: one projection for q, k, v
and the output gate ``z`` (a full ``[Hv dv]`` vector through SiLU, where
KDA's is one sigmoid a head), the softplus gate, and fewer key heads than
value heads.

A prompt of more than ``2 SEQ_BLOCK`` positions runs ``SEQ_BLOCK`` positions
at a time, each block from the state and tail the one before left: the
recurrence is the same whatever the cut, and the mixer's temporaries (its
projections and convolution in float32, q and k at the value heads' count,
the chunks' transposes: 4.2 GiB at 28,672 positions) are one block's.

Scopes ``gdn.proj``, ``gdn.scan`` and ``gdn.step`` name the parts in a
device trace.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.models import kda
from deeplearning4j_tpu.scopes import scope

__all__ = ["SEQ_BLOCK", "init_gdn", "gdn_mixer", "gdn_widths"]

# Positions a pass of the mixer takes of a prompt longer than twice this (the
# module's docstring); the serving ladders' long rungs are multiples of it.
SEQ_BLOCK = 4096


def gdn_widths(dims: Dict[str, Any]) -> Tuple[int, int]:
    """``(Hk dk, Hv dv)`` of ``dims`` = ``{key_heads, value_heads,
    head_dim, conv}`` (``TransformerLM``'s ``gdn=``; ``dk = dv =
    head_dim``)."""
    return (dims["key_heads"] * dims["head_dim"],
            dims["value_heads"] * dims["head_dim"])


def init_gdn(key, d_model: int, dims: Dict[str, Any], dtype
             ) -> Dict[str, Any]:
    """Glorot-normal ``w_qkvz`` [D, 2 Hk dk + 2 Hv dv] (columns: q, k, v,
    z), ``w_ba`` [D, 2 Hv] (columns: b, a), ``wo`` [Hv dv, D]; taps ``conv``
    [K, 2 Hk dk + Hv dv] (normal / sqrt(K)); ``a_log`` and ``dt_bias`` [Hv]
    zero; the output norm's gain ``o_norm.g`` [dv] one."""
    ks = jax.random.split(key, 4)
    ck, cv = gdn_widths(dims)
    hv, taps = dims["value_heads"], dims["conv"]

    def glorot(k, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dtype)
        return jax.random.normal(k, (fan_in, fan_out), dtype) * scale

    return {"w_qkvz": glorot(ks[0], d_model, 2 * ck + 2 * cv),
            "w_ba": glorot(ks[1], d_model, 2 * hv),
            "wo": glorot(ks[2], cv, d_model),
            "conv": jax.random.normal(ks[3], (taps, 2 * ck + cv), dtype)
            * (taps ** -0.5),
            "a_log": jnp.zeros((hv,), dtype),
            "dt_bias": jnp.zeros((hv,), dtype),
            "o_norm": {"g": jnp.ones((dims["head_dim"],), dtype)}}


def gdn_mixer(x, p: Dict[str, Any], *, dims: Dict[str, Any], eps: float,
              cast: Callable = lambda w: w, live=None,
              state=None, kernel: bool = False) -> Tuple[Any, Any, Any]:
    """The mixer on ``x`` [b, t, D] with the block's ``gdn`` parameters
    ``p`` (``init_gdn``); ``eps`` is the output norm's. ``live``,
    ``kernel`` and ``state`` = ``(S [b, Hv, dk, dv] float32, tail [b, K-1,
    2 Hk dk + Hv dv])`` as ``kda.kda_mixer``'s.

    Returns ``(y [b, t, D] in x.dtype, S, tail)``: the state and the
    convolution tail as of each row's last live position."""
    b, t, _ = x.shape
    hk, hv, dk = dims["key_heads"], dims["value_heads"], dims["head_dim"]
    ck, cv = gdn_widths(dims)
    f32 = jnp.float32
    if t > 2 * SEQ_BLOCK and t % SEQ_BLOCK == 0:
        # block after block, the state and tail handed on (a block with no
        # live row hands on what it was given)
        if state is None:
            state = (jnp.zeros((b, hv, dk, dk), f32), jnp.zeros(
                (b, p["conv"].shape[0] - 1, 2 * ck + cv), x.dtype))
        if live is None:
            live = jnp.ones((b, t), bool)

        def blocks(a):      # [b, n B, ...] -> [n, b, B, ...]
            return jnp.moveaxis(
                a.reshape((b, -1, SEQ_BLOCK) + a.shape[2:]), 1, 0)

        def one(carry, rows):
            y, s, tail = gdn_mixer(rows[0], p, dims=dims, eps=eps, cast=cast,
                                   live=rows[1], state=carry)
            return (s, tail), y

        (s, tail), y = lax.scan(one, state, (blocks(x), blocks(live)))
        return jnp.moveaxis(y, 0, 1).reshape(x.shape), s, tail
    with scope("gdn.proj"):
        proj = x @ cast(p["w_qkvz"])
        z = proj[..., 2 * ck + cv:].reshape(b, t, hv, dk)
        s0, tail, rows, mixed = kda.conv_rows(
            proj[..., :2 * ck + cv], p["conv"], state)
        q = kda.l2norm(mixed[..., :ck].reshape(b, t, hk, dk)) * dk ** -0.5
        k = kda.l2norm(mixed[..., ck:2 * ck].reshape(b, t, hk, dk))
        v = mixed[..., 2 * ck:].reshape(b, t, hv, dk)
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        ba = (x @ cast(p["w_ba"])).astype(f32)
        beta = jax.nn.sigmoid(ba[..., :hv])                     # [b, t, Hv]
        g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + p["dt_bias"].astype(f32))
        g, beta = kda.mask_dead(g[..., None], beta, live)
        new_tail = kda.live_tail(rows, live, p["conv"].shape[0])
        if s0 is None:
            s0 = jnp.zeros((b, hv, dk, dk), f32)
    o, s = kda.recur(q, k, v, g, beta, state, s0, ("gdn.step", "gdn.scan"),
                     live, kernel)
    with scope("gdn.proj"):
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
            * p["o_norm"]["g"].astype(f32)
        o = (o * jax.nn.silu(z.astype(f32))).astype(x.dtype)
        y = o.reshape(b, t, -1) @ cast(p["wo"])
    return y, s, new_tail.astype(tail.dtype)
