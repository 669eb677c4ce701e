"""Plain reference for a pre-norm decoder-only LM (StarCoder2's block).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, and nothing imported from the program. It reads the program's
parameter tree as data (``embed``, ``blocks[i].{ln1,attn,ln2,mlp}``,
``ln_f``) and follows the published block:

    h  = embed[tokens]
    a  = LayerNorm(h; g1, b1, eps)
    q, k, v = a Wq, a Wk, a Wv          (GQA: H query heads, Hkv kv heads)
    q, k = RoPE(q), RoPE(k)             (rotate-half, base ``rope_theta``)
    o  = softmax(q k^T / sqrt(Dh) + causal sliding-window mask) v
    h  = h + o Wo
    h  = h + W2 gelu_tanh(LayerNorm(h; g2, b2, eps) W1 + c1) + c2
    logits = LayerNorm(h; gf, bf, eps) embed^T          (tied)

Departures from ``bigcode/starcoder2-3b`` are the configuration file's
(``departures``): no bias on the attention projections, and ``rope_theta``
as the file gives it. Attention runs in query blocks against the key band
the window allows, so a 16k context fits; that changes memory, not
arithmetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layernorm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g.astype(F32) + b.astype(F32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _rope(x, theta):
    """x: [T, H, Dh] at positions 0..T-1."""
    t, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window, q_block):
    """q: [T, H, Dh]; k, v: [T, Hkv, Dh]. Causal, key in (query - window,
    query]."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    outs = []
    for s in range(0, t, q_block):
        e = min(t, s + q_block)
        k0 = 0 if window is None else max(0, s - window + 1)
        qb = q[s:e].reshape(e - s, hkv, rep, dh)
        kb, vb = k[k0:e], v[k0:e]
        scores = jnp.einsum("qgrd,kgd->grqk", qb, kb) / math.sqrt(dh)
        qi = jnp.arange(s, e)[:, None]
        ki = jnp.arange(k0, e)[None, :]
        ok = ki <= qi
        if window is not None:
            ok &= ki > qi - window
        scores = jnp.where(ok[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("grqk,kgd->qgrd", p, vb).reshape(e - s, h * dh))
    return jnp.concatenate(outs, axis=0)


def hidden_states(params, tokens, cfg, q_block=2048):
    """tokens [T] -> final-normed hidden states [T, D], float32."""
    h_heads = cfg["num_attention_heads"]
    kv_heads = cfg["num_key_value_heads"]
    eps = cfg["norm_epsilon"]
    theta = cfg["rope_theta"]
    window = cfg.get("sliding_window")
    h = params["embed"].astype(F32)[tokens]
    t = h.shape[0]
    for blk in params["blocks"]:
        a = _layernorm(h, blk["ln1"]["g"], blk["ln1"]["b"], eps)
        q = (a @ blk["attn"]["wq"].astype(F32)).reshape(t, h_heads, -1)
        k = (a @ blk["attn"]["wk"].astype(F32)).reshape(t, kv_heads, -1)
        v = (a @ blk["attn"]["wv"].astype(F32)).reshape(t, kv_heads, -1)
        o = _attention(_rope(q, theta), _rope(k, theta), v, window, q_block)
        h = h + o @ blk["attn"]["wo"].astype(F32)
        m = _layernorm(h, blk["ln2"]["g"], blk["ln2"]["b"], eps)
        m = _gelu_tanh(m @ blk["mlp"]["w1"].astype(F32)
                       + blk["mlp"]["b1"].astype(F32))
        h = h + m @ blk["mlp"]["w2"].astype(F32) + blk["mlp"]["b2"].astype(F32)
    return _layernorm(h, params["ln_f"]["g"], params["ln_f"]["b"], eps)


def _logits(params, hidden):
    return hidden @ params["embed"].astype(F32).T


@functools.lru_cache(maxsize=None)
def _jit_logits_tail(cfg_key, n_tail):
    cfg = dict(cfg_key)

    def f(params, tokens, real_len):
        hid = hidden_states(params, tokens, cfg)
        # the last n_tail real positions: real_len - n_tail .. real_len - 1
        tail = jax.lax.dynamic_slice_in_dim(hid, real_len - n_tail, n_tail)
        return _logits(params, tail)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _jit_mean_nll(cfg_key, block):
    cfg = dict(cfg_key)

    def f(params, tokens):
        hid = hidden_states(params, tokens, cfg)
        t = tokens.shape[0]
        total = jnp.zeros((), F32)
        for s in range(0, t - 1, block):
            e = min(t - 1, s + block)
            logp = jax.nn.log_softmax(_logits(params, hid[s:e]), axis=-1)
            total += -jnp.sum(jnp.take_along_axis(
                logp, tokens[s + 1:e + 1, None], axis=-1))
        return total / (t - 1)

    return jax.jit(f)


def _key(cfg):
    keep = ("num_attention_heads", "num_key_value_heads", "norm_epsilon",
            "rope_theta", "sliding_window")
    return tuple((k, cfg.get(k)) for k in keep)


def tail_logits(params, tokens, cfg, n_tail, pad_to=None):
    """Teacher-forced logits at the last ``n_tail`` positions of ``tokens``
    ([T] ints) against the whole context -> [n_tail, V] float32. ``pad_to``
    pads the sequence on the right (causality makes the pad inert) so that
    few lengths compile."""
    tokens = jnp.asarray(tokens, jnp.int32)
    real = int(tokens.shape[0])
    if pad_to is not None and pad_to > real:
        tokens = jnp.pad(tokens, (0, pad_to - real))
    with jax.default_matmul_precision("highest"):
        return _jit_logits_tail(_key(cfg), int(n_tail))(
            params, tokens, jnp.asarray(real, jnp.int32))


def mean_nll(params, tokens, cfg, block=2048):
    """Mean next-token negative log-likelihood over one sequence [T]."""
    with jax.default_matmul_precision("highest"):
        return _jit_mean_nll(_key(cfg), block)(
            params, jnp.asarray(tokens, jnp.int32))
