"""Plain reference for ``manifestai/Brumby-14B-Base`` (``brumby``): every
layer is power retention (power attention, arXiv:2507.04239, with the gate
of Manifest AI's ``retention`` package), degree ``p`` = 2, then a SwiGLU.
With ``x`` the block's normed input, per key/value head ``h`` of ``Hkv``
serving the query heads ``h H/Hkv .. (h + 1) H/Hkv - 1``, ``d`` the head
size:

    q, k, v  = x W_q, x W_k, x W_v                       no bias
    q, k     = rope(rmsnorm_head(q)), rope(rmsnorm_head(k))   pairs (i, i + d/2)
    log g_t  = logsigmoid(x_t W_g + b_g)                 one a key/value head
    w_ij     = (q_i . k_j / sqrt(d))^p exp(sum_{l=j+1..i} log g_l)   j <= i
    o_i      = sum_j w_ij v_j / (sum_j w_ij + eps)
    y        = concat_heads(o) W_o
    ffn      = W_down(silu(x W_gate) * x W_up)

This is the **quadratic form**: every query against every key before it,
``[T, T]`` weights a head. No state, no ``phi``, no chunks, no cache, no
batching, no kernels, and nothing imported from the program: what the
program computes as a recurrence on a ``[D, d]`` state is here a masked
matrix of powers. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. The gates' product between two
positions is taken as ``exp(G_i - G_j)`` of the cumulated log-gates in
float32 (at 28k positions and ``log g`` down to -0.3 the cumulated sum
reaches -9,000: a quotient of two ``exp`` would be 0 / 0).

It reads the program's parameter tree as data: ``embed``, ``head``,
``ln_f.g``, ``blocks[i].{ln1.g, ln2.g, ret.{wq, wk, wv, wo, wg, bg,
q_norm.g, k_norm.g}, glu.{w1, w3, w2}}``.

Departures from the published model, each the configuration file's too:

- a norm's gain is read as stored, ``g`` (the published Qwen3 norm's ``w``);
- the unembedding is read as ``head`` [V, D] and applied as ``h head^T``;
- the queries run in blocks of ``Q_BLOCK`` so that an 18k-token context fits
  beside the weights: memory, not arithmetic.

Controls (``benchmarks/tools/float8_reference_brumby.py``; ``cfg["control"]``):
``no_decay`` puts every gate at 1 (a sum that never forgets), ``p4`` raises
the scores to the fourth power, ``softmax`` takes ``exp`` of the score in
place of the power (the gates and the normaliser kept). A check that passes
one of them does not see that part of the mechanism.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512
KEYS = ("rms_norm_eps", "rope_theta", "num_attention_heads",
        "num_key_value_heads", "head_dim", "power", "ret_eps")


def _rmsnorm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g.astype(F32)


def _rope(x, theta):
    """``x`` [T, H, d] at positions 0 .. T-1: pairs (i, i + d/2)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]      # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def retention(q, k, v, lg, cfg):
    """``q`` [T, H, d], ``k``, ``v`` [T, Hkv, d], ``lg`` [T, Hkv] (the
    log-gates) -> ``o`` [T, H, d]: the quadratic form, a block of queries at
    a time."""
    t, h, d = q.shape
    hkv = k.shape[1]
    control = cfg.get("control")
    power = 4 if control == "p4" else cfg["power"]
    if control == "no_decay":
        lg = jnp.zeros_like(lg)
    cum = jnp.cumsum(lg, axis=0)                                # G_t [T, Hkv]
    block = min(Q_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions do not split into blocks of {block}")
    qg = q.reshape(t // block, block, hkv, h // hkv, d)
    at = jnp.arange(t).reshape(t // block, block)
    cums = cum.reshape(t // block, block, hkv)

    def one(args):
        qb, pos, cb = args          # [B, Hkv, r, d], [B], [B, Hkv]
        s = jnp.einsum("ihrd,jhd->hrij", qb, k) / jnp.sqrt(jnp.asarray(d, F32))
        seen = (jnp.arange(t)[None, :] <= pos[:, None])         # [B, T]
        gates = jnp.where(seen[None], (cb.T[:, :, None] - cum.T[:, None, :]),
                          -jnp.inf)                             # [Hkv, B, T]
        if control == "softmax":
            s = jnp.where(seen[None, None], s, -jnp.inf)
            w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)) \
                * jnp.exp(gates)[:, None]
        else:
            w = s ** power * jnp.exp(gates)[:, None]
        o = jnp.einsum("hrij,jhd->ihrd", w, v)
        return o / (jnp.sum(w, axis=-1).transpose(2, 0, 1)[..., None]
                    + cfg["ret_eps"])

    return jax.lax.map(one, (qg, at, cums)).reshape(t, h, d)


def ret_mixer(x, p, cfg):
    t = x.shape[0]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = (x @ p["wq"].astype(F32)).reshape(t, h, d)
    k = (x @ p["wk"].astype(F32)).reshape(t, hkv, d)
    v = (x @ p["wv"].astype(F32)).reshape(t, hkv, d)
    q = _rope(_rmsnorm(q, p["q_norm"]["g"], eps), theta)
    k = _rope(_rmsnorm(k, p["k_norm"]["g"], eps), theta)
    lg = jax.nn.log_sigmoid(x @ p["wg"].astype(F32) + p["bg"].astype(F32))
    o = retention(q, k, v, lg, cfg)
    return o.reshape(t, h * d) @ p["wo"].astype(F32)


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["w1"].astype(F32))
            * (x @ p["w3"].astype(F32))) @ p["w2"].astype(F32)


def hidden_states(params, tokens, cfg):
    """tokens [T] -> final-normed hidden states [T, D]."""
    eps = cfg["rms_norm_eps"]
    h = params["embed"].astype(F32)[tokens]
    for blk in params["blocks"]:
        h = h + ret_mixer(_rmsnorm(h, blk["ln1"]["g"], eps), blk["ret"], cfg)
        h = h + _swiglu(_rmsnorm(h, blk["ln2"]["g"], eps), blk["glu"])
    return _rmsnorm(h, params["ln_f"]["g"], eps)


def _logits(params, hidden):
    return hidden @ params["head"].astype(F32).T


def _key(cfg):
    return tuple((k, cfg[k]) for k in KEYS) + (
        ("control", cfg.get("control")),)


@functools.lru_cache(maxsize=None)
def _jit_tail(cfg_key, n_tail):
    cfg = dict(cfg_key)

    def f(params, tokens, real_len):
        hid = hidden_states(params, tokens, cfg)
        start = jnp.maximum(real_len - n_tail, 0)
        return _logits(params, jax.lax.dynamic_slice_in_dim(
            hid, start, n_tail))

    return jax.jit(f)


def forward_tail(params, tokens, cfg, n_tail, pad_to=None):
    """One forward over ``tokens`` ([T] ints): teacher-forced float32 logits
    at the last ``min(n_tail, T)`` positions against the whole context.
    ``pad_to`` pads the sequence on the right so that few lengths compile:
    every weight is causal, so the pad is inert for the positions before
    it."""
    tokens = jnp.asarray(tokens, jnp.int32)
    real = int(tokens.shape[0])
    if pad_to is not None and pad_to > real:
        tokens = jnp.pad(tokens, (0, pad_to - real))
    n_tail = min(int(n_tail), int(tokens.shape[0]))
    with jax.default_matmul_precision("highest"):
        logits = _jit_tail(_key(cfg), n_tail)(
            params, tokens, jnp.asarray(real, jnp.int32))
    return logits[:min(n_tail, real)]


def loss(params, tokens, cfg):
    """Mean next-token cross entropy of one sequence [T] (what the
    program's ``loss`` computes for a batch of one)."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(
            _logits(params, hidden_states(params, tokens, cfg)), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp[:-1], tokens[1:, None], axis=-1))
