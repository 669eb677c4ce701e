"""Kimi Delta Attention (KDA, arXiv:2510.26692 section 3): the linear-attention
mixer of a hybrid block, as ``TransformerLM._block`` runs it.

Per head, with a recurrent state ``S`` [dk, dv] in float32, zero at a
request's start (``x`` is the block's normed input):

    q~, k~, v~ = x Wq, x Wk, x Wv
    q, k, v    = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                 conv: depthwise causal convolution over time, no bias
    q, k       = q / ||q||, k / ||k||  per head;  q = q dk^-1/2
    g_t        = lower * sigmoid(exp(A_log) * (x Wa + dt_bias))   in (lower, 0)
    beta_t     = sigmoid(x Wb)                                    one a head
    S'         = diag(exp(g_t)) S_{t-1}
    S_t        = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t        = S_t^T q_t
    y          = concat_h(rmsnorm(o_t) * sigmoid(x Wg)_h) Wo

Two forms of the same recurrence:

- ``kda_scan`` (prefill, training): chunks of ``CHUNK`` positions. Inside
  a chunk the delta rule is one unit-lower-triangular system (the WY form of
  the paper's section 3.2), solved by the inverse's finite series; across
  chunks the state is a ``lax.scan``
  carry. The decay between two positions of a chunk is taken as
  ``exp(G_t - G_i)`` of the cumulated log-decays themselves, never as a
  quotient ``exp(G_t) / exp(G_i)``: with ``lower = -5`` a chunk's
  cumulated decay leaves float32's range.
- ``kda_step`` (decode): the recurrence itself, one token a row, as XLA
  ops over every row. With one decay a head (``models/gdn.py``) a serving
  decode step runs it as ``pallas/delta_step.py`` instead
  (``recur(kernel=True)``): the same arithmetic over the rows that owe a
  token only, their state moved once, in place. ``kda_step`` is the form
  that kernel is held to, and the path where it does not apply (a pool
  sharded over a mesh, a plain loop) or is not yet taken (``recur``).

A row that holds no token (``live`` false: a prompt's pad tail, a slot
that owes nothing) takes ``beta = 0`` and ``g = 0`` and so leaves the state
as it was, and the convolution tail is that of the last real positions.
A pad tail is inert for attention by causality; for a recurrence it has to
be made so, here.

Scopes ``kda.proj``, ``kda.scan`` and ``kda.step`` name the parts in a
device trace.

The decay may also be **one number a head** (Gated DeltaNet, arXiv:2412.06464:
``S' = exp(g_t) S_{t-1}``, ``models/gdn.py``): ``g`` then has a last axis of
1 where KDA's has ``dk``. Both forms take it so. It is the same recurrence,
the same chunk loop, inverse and state update; only the products inside a
chunk differ: a scalar decay leaves ``k_t . k_i`` and ``q_t . k_i`` plain
matmuls, each times ``exp(G_t - G_i)``, a ``[c, c]`` matrix a head, where a
per-channel decay has to enter every term of the dot product (``[c, c, dk]``
on the VPU).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.pallas.delta_step import delta_step
from deeplearning4j_tpu.scopes import scope

__all__ = ["CHUNK", "init_kda", "kda_mixer", "kda_scan", "kda_step",
           "conv_rows", "live_tail", "mask_dead", "recur", "l2norm"]

CHUNK = 64
_HI = lax.Precision.HIGHEST
_EPS = 1e-6


def init_kda(key, d_model: int, num_heads: int, head_dim: int, conv: int,
             dtype, gate_rank: int = 0, out_gate: str = "head"
             ) -> Dict[str, Any]:
    """Glorot-normal projections ``wq/wk/wv/wa`` [D, H dk], ``wo``
    [H dk, D], ``wb/wg`` [D, H]; convolution taps ``conv_q/k/v``
    [conv, H dk] (normal / sqrt(conv)); ``a_log`` [H] zero, ``dt_bias``
    [H dk] zero, the output norm's gain ``o_norm.g`` [dk] one.

    Kimi Linear's published forms of the two gates, beside Ling's:
    ``gate_rank`` = r > 0: the decay's projection is low-rank, ``wa_down``
    [D, r] and ``wa_up`` [r, H dk] in place of ``wa``; ``out_gate`` =
    ``"channel"``: the output gate is one number a channel through the same
    rank, ``wg_down`` [D, r] and ``wg_up`` [r, H dk] in place of the
    head-wise ``wg`` (it needs ``gate_rank``)."""
    if out_gate not in ("head", "channel") or (out_gate == "channel"
                                               and not gate_rank):
        raise ValueError(
            f"kda: out_gate={out_gate!r} is 'head' or 'channel', and a gate "
            f"a channel goes through gate_rank (got {gate_rank})")
    ks = jax.random.split(key, 10)
    d, c = d_model, num_heads * head_dim

    def glorot(k, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dtype)
        return jax.random.normal(k, (fan_in, fan_out), dtype) * scale

    def taps(k):
        return jax.random.normal(k, (conv, c), dtype) * (conv ** -0.5)

    p = {"wq": glorot(ks[0], d, c), "wk": glorot(ks[1], d, c),
         "wv": glorot(ks[2], d, c), "wa": glorot(ks[3], d, c),
         "wb": glorot(ks[4], d, num_heads),
         "wg": glorot(ks[5], d, num_heads),
         "wo": glorot(ks[6], c, d),
         "conv_q": taps(ks[7]), "conv_k": taps(ks[8]),
         "conv_v": taps(ks[9]),
         "a_log": jnp.zeros((num_heads,), dtype),
         "dt_bias": jnp.zeros((c,), dtype),
         "o_norm": {"g": jnp.ones((head_dim,), dtype)}}
    if gate_rank:
        del p["wa"]
        p["wa_down"] = glorot(ks[3], d, gate_rank)
        p["wa_up"] = glorot(jax.random.fold_in(ks[3], 1), gate_rank, c)
    if out_gate == "channel":
        del p["wg"]
        p["wg_down"] = glorot(ks[5], d, gate_rank)
        p["wg_up"] = glorot(jax.random.fold_in(ks[5], 1), gate_rank, c)
    return p


def l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _EPS)


def _unit_lower_inverse(n, eye, mm):
    """``(I + n)^-1`` for strictly lower triangular ``n`` [..., c, c]: n is
    nilpotent, so the series ``sum_j (-n)^j`` ends at ``c - 1`` and factors
    as ``(I - n)(I + n^2)(I + n^4)...``: a dozen small matmuls on the MXU
    where a triangular solve is a sequential custom call on the TPU (its 64
    rows one after another, the largest single op of a prefill; PERF.md
    section 6, PR 29)."""
    inv, power, reach = eye - n, n, 2
    while reach < n.shape[-1]:
        power = mm("...ij,...jk->...ik", power, power)
        inv = mm("...ij,...jk->...ik", inv, eye + power)
        reach *= 2
    return inv


def kda_scan(q, k, v, g, beta, state):
    """The chunked recurrence. ``q, k`` [b, t, H, dk], ``v`` [b, t, H, dv],
    ``g`` [b, t, H, dk] (log-decay, <= 0; [b, t, H, 1]: one decay a head),
    ``beta`` [b, t, H], all float32; ``state`` [b, H, dk, dv]. Returns
    ``(o [b, t, H, dv], state)``."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(CHUNK, t)
    pad = -t % c
    if pad:     # beta = 0 and g = 0: the tail moves no state
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    n = (t + pad) // c

    def chunks(a):      # [b, n c, H, ...] -> [n, b, H, c, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 3, 2)

    keep = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    eye = jnp.eye(c, dtype=jnp.float32)

    def mm(spec, x, y):
        return jnp.einsum(spec, x, y, precision=_HI)

    def step(s, xs):
        qc, kc, vc, gc, bc = xs                 # [b, H, c, dk] ... [b, H, c]
        cum = jnp.cumsum(gc, axis=2)            # G_t
        if gc.shape[-1] == 1:
            # one decay a head: exp(G_t - G_i) is a [c, c] matrix and the
            # dot products are the MXU's
            decay = jnp.exp(jnp.where(
                keep, cum - jnp.swapaxes(cum, 2, 3), -jnp.inf))
            a = mm("bhtk,bhik->bhti", kc, kc) * decay       # k_t . k_i
            qk = mm("bhtk,bhik->bhti", qc, kc) * decay      # q_t . k_i
        else:
            # decay from position i to position t >= i, per channel
            rel = jnp.where(keep[:, :, None],
                            cum[:, :, :, None, :] - cum[:, :, None, :, :],
                            -jnp.inf)
            kd = kc[:, :, None, :, :] * jnp.exp(rel)        # [b,H,t,i,dk]
            a = jnp.sum(kc[:, :, :, None, :] * kd, axis=-1)   # k_t . k_i
            qk = jnp.sum(qc[:, :, :, None, :] * kd, axis=-1)  # q_t . k_i
        into = jnp.exp(cum)
        rhs = bc[..., None] * (vc - mm("bhtk,bhkv->bhtv", kc * into, s))
        w = mm("bhti,bhiv->bhtv", _unit_lower_inverse(
            bc[..., None] * jnp.where(strict, a, 0.0), eye, mm), rhs)
        o = mm("bhtk,bhkv->bhtv", qc * into, s) + mm(
            "bhti,bhiv->bhtv", qk, w)
        last = cum[:, :, -1:, :]
        s = jnp.swapaxes(jnp.exp(last), 2, 3) * s + mm(
            "bhik,bhiv->bhkv", kc * jnp.exp(last - cum), w)
        return s, o

    state, o = lax.scan(step, state,
                        tuple(chunks(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)          # [b, n, c, H, dv]
    return o.reshape(b, n * c, h, dv)[:, :t], state


def kda_step(q, k, v, g, beta, state):
    """The recurrence for one position a row: ``q, k, g`` [b, H, dk] (``g``
    [b, H, 1]: one decay a head), ``v`` [b, H, dv], ``beta`` [b, H], float32;
    ``state`` [b, H, dk, dv]. Returns ``(o [b, H, dv], state)``."""
    # one pass over the state gives both S'^T k and S'^T q (S' = diag(a) S,
    # so S'^T x = S^T (a * x)); o = S_t^T q = S'^T q + beta (k . q) u
    decay = jnp.exp(g)
    sk, sq = jnp.moveaxis(jnp.einsum(
        "bhkv,bhjk->bhjv", state, jnp.stack([decay * k, decay * q], axis=2),
        precision=_HI), 2, 0)
    u = beta[..., None] * (v - sk)
    o = sq + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, decay[..., None] * state + k[..., None] * u[:, :, None, :]


def _conv(rows, taps):
    """Depthwise causal convolution with ``taps`` [K, C] over ``rows``
    [b, K-1 + t, C]: the K-1 positions before the first output, then the t
    positions themselves. Returns [b, t, C]."""
    width = taps.shape[0]
    t = rows.shape[1] - (width - 1)
    return sum(rows[:, j:j + t] * taps[j] for j in range(width))


def conv_rows(qkv, taps, state):
    """The short convolution of a delta-rule mixer: ``qkv`` [b, t, C] (the
    projections side by side) after the ``K - 1`` rows the positions before
    left, through ``taps`` [K, C] and SiLU. ``state`` = ``(S, tail [b, K-1,
    C])`` or None (a request's start: a tail of zeros, no matrix yet).
    Returns ``(S or None, tail, rows [b, K-1 + t, C], mixed [b, t, C]
    float32)``."""
    b = qkv.shape[0]
    if state is None:
        s0 = None
        tail = jnp.zeros((b, taps.shape[0] - 1, qkv.shape[-1]), qkv.dtype)
    else:
        s0, tail = state
    rows = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)
    mixed = jax.nn.silu(_conv(rows.astype(jnp.float32),
                              taps.astype(jnp.float32)))
    return s0, tail, rows, mixed


def mask_dead(g, beta, live):
    """The rule for a row that holds no token: ``beta = 0`` and ``g = 0``
    (``g`` [b, t, H, .], ``beta`` [b, t, H], ``live`` [b, t] or None)."""
    if live is None:
        return g, beta
    return (jnp.where(live[:, :, None, None], g, 0.0),
            jnp.where(live[:, :, None], beta, 0.0))


def live_tail(rows, live, width: int):
    """The convolution tail as of each row's last live position: the
    ``width - 1`` rows of ``rows`` [b, K-1 + t, C] that end there."""
    b, t = rows.shape[0], rows.shape[1] - (width - 1)
    n_live = (jnp.full((b,), t) if live is None
              else jnp.sum(live, axis=1))
    idx = n_live[:, None] + jnp.arange(width - 1)[None, :]
    return jnp.take_along_axis(rows, idx[:, :, None], axis=1)


def recur(q, k, v, g, beta, state, s0, names, live=None, kernel=False):
    """One position on a carried state, else ``kda_scan``, under the mixer's
    own scope names ``(step, scan)``. ``q, k, v, g, beta`` [b, t, H, .]
    (``g``, ``beta`` through ``mask_dead``); ``state``: what the mixer was
    handed; ``s0`` the matrix to start from. The one position is
    ``kda_step``, or with ``kernel`` and one decay a head the Pallas step
    over the rows of ``live`` [b, 1] (``pallas/delta_step.py``, inside the
    scope, which names its instruction in a trace). Either way a row that is
    not live gives zeros: the kernel never reads its state.

    A decay a channel (KDA) stays on ``kda_step`` for now, though the kernel
    takes it and is as fast there (its docstring): with it
    ``ling-serve-reason`` read ``kda_roofline`` 110 %, because that metric's
    numerator counts the mixers' weights whose prefetch waits its time does
    not (ROADMAP S2 (b0) item 7, PERF.md sections 6 and 7); the branch goes
    when the reader is put right."""
    if state is not None and q.shape[1] == 1:
        with scope(names[0]):
            rows = (a[:, 0] for a in (q, k, v, g, beta))
            if kernel and g.shape[-1] == 1:
                o, s = delta_step(
                    *rows, s0, None if live is None else live[:, 0])
            else:
                o, s = kda_step(*rows, s0)
                if live is not None:
                    o = jnp.where(live[:, :, None], o, 0.0)
            return o[:, None], s
    with scope(names[1]):
        return kda_scan(q, k, v, g, beta, s0)


def kda_mixer(x, p: Dict[str, Any], *, num_heads: int, lower: float,
              cast: Callable = lambda w: w, live=None,
              state=None, kernel: bool = False) -> Tuple[Any, Any, Any]:
    """The mixer on ``x`` [b, t, D] with the block's ``kda`` parameters
    ``p`` (``init_kda``). ``live`` [b, t] (bool, default all) marks the
    rows that hold a token; within a row they are a prefix. ``state`` =
    ``(S [b, H, dk, dv] float32, tail [b, K-1, 3 H dk])`` is what the
    positions before ``x`` left (default: a request's start, zeros); with a
    state and ``t == 1`` the recurrence runs as ``kda_step``, or with
    ``kernel`` as the Pallas step over the live rows (``recur``).

    Returns ``(y [b, t, D] in x.dtype, S, tail)``: the state and the
    convolution tail as of each row's last live position."""
    b, t, _ = x.shape
    h = num_heads
    f32 = jnp.float32
    with scope("kda.proj"):
        qkv = jnp.concatenate(
            [x @ cast(p[n]) for n in ("wq", "wk", "wv")], axis=-1)
        width = p["conv_q"].shape[0]
        taps = jnp.concatenate(
            [p[n] for n in ("conv_q", "conv_k", "conv_v")], axis=-1)
        s0, tail, rows, mixed = conv_rows(qkv, taps, state)
        q, k, v = (a.reshape(b, t, h, -1) for a in jnp.split(mixed, 3, -1))
        dk = q.shape[-1]
        q = l2norm(q) * dk ** -0.5
        k = l2norm(k)
        # the parameters say which form each gate has (``init_kda``)
        a = (x @ cast(p["wa"]) if "wa" in p
             else (x @ cast(p["wa_down"])) @ cast(p["wa_up"]))
        a = a.astype(f32) + p["dt_bias"].astype(f32)
        g = lower * jax.nn.sigmoid(
            jnp.exp(p["a_log"].astype(f32))[:, None] * a.reshape(b, t, h, dk))
        beta = jax.nn.sigmoid((x @ cast(p["wb"])).astype(f32))   # [b, t, H]
        if "wg" in p:       # one number a head
            gate = jax.nn.sigmoid((x @ cast(p["wg"])).astype(f32))
        else:               # one a channel
            gate = jax.nn.sigmoid(
                ((x @ cast(p["wg_down"])) @ cast(p["wg_up"])).astype(f32)
            ).reshape(b, t, h, -1)
        g, beta = mask_dead(g, beta, live)
        new_tail = live_tail(rows, live, width)
        if s0 is None:
            s0 = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    o, s = recur(q, k, v, g, beta, state, s0, ("kda.step", "kda.scan"),
                 live, kernel)
    with scope("kda.proj"):
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + _EPS) \
            * p["o_norm"]["g"].astype(f32)
        o = (o * (gate[..., None] if gate.ndim == 3 else gate)).astype(
            x.dtype).reshape(b, t, -1)
        y = o @ cast(p["wo"])
    return y, s, new_tail.astype(tail.dtype)
