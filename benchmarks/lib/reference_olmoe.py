"""Plain reference for OLMoE's block (arXiv:2409.02060; ``modeling_olmoe.py``
of ``allenai/OLMoE-1B-7B-0125-Instruct``).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a loop over the experts with a
mask (no sort, no grouped matmul), no kernels, no cache, no batching, and
nothing imported from the program. It reads the program's parameter tree as
data (``embed``, ``head``, ``ln_f.g``, ``blocks[i].{ln1.g, attn.{wq, wk, wv,
wo, q_norm.g, k_norm.g}, ln2.g, moe.{router, w_gate, w_up, w_down}}``) and
follows the published block:

    x  = RMSNorm(h; g1)                     weight only, eps, float32
    q  = RMSNorm(x Wq; gq)   k = RMSNorm(x Wk; gk)     over the whole
                                            projection, before the heads
    v  = x Wv                               no bias anywhere
    q, k = RoPE(q), RoPE(k)                 rotate-half, base rope_theta
    h  = h + CausalAttention(q, k, v) Wo    full causal
    x  = RMSNorm(h; g2)
    p  = softmax(x Wr)                      over all experts
    (w_j, e_j) = top_k(p)                   raw probabilities unless
                                            norm_topk_prob
    h  = h + sum_j w_j ((silu(x Wgate[e_j]) * (x Wup[e_j])) Wdown[e_j])
    logits = RMSNorm(h_L; gf) Whead         Whead is not the embedding

Departures from the published model, each the configuration file's too:

- the unembedding is read as ``head`` [V, D] and applied as ``h head^T``
  (the layout of ``lm_head.weight``; the program stores it so);
- attention runs in query blocks so that a 4,096-token context fits beside
  the weights: memory, not arithmetic;
- every expert runs on every token and a mask keeps the chosen ones, where
  the published code gathers each expert's tokens: the same sum, in expert
  order rather than top-k order;
- the auxiliary load-balance and router z losses of the training recipe are
  not part of ``mean_nll``: it is the plain next-token loss.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, theta):
    """x: [T, H, Dh] at positions 0..T-1."""
    t, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, q_block):
    """q: [T, H, Dh]; k, v: [T, Hkv, Dh]. Full causal."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    outs = []
    for s in range(0, t, q_block):
        e = min(t, s + q_block)
        qb = q[s:e].reshape(e - s, hkv, rep, dh)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k[:e]) / math.sqrt(dh)
        ok = jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None]
        p = jax.nn.softmax(jnp.where(ok[None, None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("grqk,kgd->qgrd", p, v[:e]).reshape(
            e - s, h * dh))
    return jnp.concatenate(outs, axis=0)


def _route(x, router, cfg, chosen=None):
    """x [T, D] -> (weights [T, k], experts [T, k], lead [T], shortfall
    [T]). The experts are the k most probable, largest first, unless
    ``chosen`` [T, k] names them; the weights are this router's
    probabilities of those experts. ``lead`` is how far the k-th most
    probable expert leads the next one, ``shortfall`` how far the least
    probable expert used falls short of the k-th most probable (0 for a
    top-k set), both as shares of the k-th probability."""
    k = cfg["num_experts_per_tok"]
    p = jax.nn.softmax(x @ router.astype(F32), axis=-1)
    top, own = jax.lax.top_k(p, k + 1)
    kth = top[:, k - 1]
    e = own[:, :k] if chosen is None else chosen
    w = jnp.take_along_axis(p, e, axis=-1)
    shortfall = jnp.maximum(kth - jnp.min(w, axis=-1), 0.0) / kth
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, e, (kth - top[:, k]) / kth, shortfall


def _experts(x, w, e, moe):
    """One expert after another on every token; a token keeps the result
    of an expert it chose, times that expert's weight."""
    def one(i, out):
        wi = jnp.sum(jnp.where(e == i, w, 0.0), axis=-1, keepdims=True)
        hid = jax.nn.silu(x @ moe["w_gate"][i].astype(F32)) \
            * (x @ moe["w_up"][i].astype(F32))
        return out + wi * (hid @ moe["w_down"][i].astype(F32))

    return jax.lax.fori_loop(0, moe["router"].shape[1], one,
                             jnp.zeros_like(x))


def _forward(params, tokens, cfg, chosen=None, q_block=1024):
    """tokens [T] -> (final-normed hidden states [T, D], per layer the
    routing ``(weights, experts, lead, shortfall)`` of ``_route``).
    ``chosen`` [L, T, k] makes every layer use those experts."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    h = params["embed"].astype(F32)[tokens]
    t = h.shape[0]
    routes = []
    for li, blk in enumerate(params["blocks"]):
        a = blk["attn"]
        x = _rmsnorm(h, blk["ln1"]["g"], eps)
        q = _rmsnorm(x @ a["wq"].astype(F32), a["q_norm"]["g"], eps)
        k = _rmsnorm(x @ a["wk"].astype(F32), a["k_norm"]["g"], eps)
        v = (x @ a["wv"].astype(F32)).reshape(t, kv_heads, -1)
        o = _attention(_rope(q.reshape(t, heads, -1), theta),
                       _rope(k.reshape(t, kv_heads, -1), theta), v, q_block)
        h = h + o @ a["wo"].astype(F32)
        x = _rmsnorm(h, blk["ln2"]["g"], eps)
        route = _route(x, blk["moe"]["router"], cfg,
                       None if chosen is None else chosen[li])
        routes.append(route)
        h = h + _experts(x, route[0], route[1], blk["moe"])
    return _rmsnorm(h, params["ln_f"]["g"], eps), routes


def _logits(params, hidden):
    return hidden @ params["head"].astype(F32).T


@functools.lru_cache(maxsize=None)
def _jit_tail(cfg_key, n_tail):
    cfg = dict(cfg_key)

    def f(params, tokens, real_len, chosen):
        hid, routes = _forward(params, tokens, cfg, chosen)
        start = jnp.maximum(real_len - n_tail, 0)
        tail = jax.lax.dynamic_slice_in_dim(hid, start, n_tail)
        return _logits(params, tail), routes

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _jit_mean_nll(cfg_key, block):
    cfg = dict(cfg_key)

    def f(params, tokens):
        hid, _ = _forward(params, tokens, cfg)
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
    keep = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta", "num_experts_per_tok", "norm_topk_prob")
    return tuple((k, cfg[k]) for k in keep)


def forward_tail(params, tokens, cfg, n_tail, pad_to=None, chosen=None):
    """One forward over ``tokens`` ([T] ints) -> ``(logits, routes)``:
    teacher-forced float32 logits at the last ``min(n_tail, T)`` positions
    against the whole context, and each layer's routing of every position
    as ``(weights [T, k], experts [T, k], lead [T], shortfall [T])``.

    Without ``chosen`` the experts are the reference's own top k, largest
    first, and ``lead`` says how fragile that choice is: the relative lead
    of the k-th most probable expert over the next. With ``chosen``
    ([L, T, k] ints: the experts another implementation chose) the reference
    computes the model with *those* experts, weighted by its own
    probabilities of them, and ``shortfall`` says whether the choice was
    admissible: how far the least probable expert used falls short of the
    reference's k-th, as a share of it (0 where the sets agree). A router
    fed rounded activations may swap experts whose probabilities are that
    close, and nothing else; judging the logits on the same side of each
    such near-tie keeps one swapped expert (an eighth of a token's
    feed-forward) from being read as an arithmetic error.

    ``pad_to`` pads the sequence on the right (causality and the per-token
    experts make the pad inert) so that few lengths compile."""
    tokens = jnp.asarray(tokens, jnp.int32)
    real = int(tokens.shape[0])
    if chosen is not None:
        chosen = jnp.asarray(chosen, jnp.int32)[:, :real]
    if pad_to is not None and pad_to > real:
        tokens = jnp.pad(tokens, (0, pad_to - real))
        if chosen is not None:
            chosen = jnp.pad(chosen, ((0, 0), (0, pad_to - real), (0, 0)))
    n_tail = min(int(n_tail), int(tokens.shape[0]))
    with jax.default_matmul_precision("highest"):
        logits, routes = _jit_tail(_key(cfg), n_tail)(
            params, tokens, jnp.asarray(real, jnp.int32), chosen)
    return (logits[:min(n_tail, real)],
            [tuple(x[:real] for x in r) for r in routes])


def tail_logits(params, tokens, cfg, n_tail, pad_to=None):
    """The logits of ``forward_tail``: [min(n_tail, T), V] float32."""
    return forward_tail(params, tokens, cfg, n_tail, pad_to)[0]


def mean_nll(params, tokens, cfg, block=2048):
    """Mean next-token negative log-likelihood over one sequence [T]."""
    with jax.default_matmul_precision("highest"):
        return _jit_mean_nll(_key(cfg), block)(
            params, jnp.asarray(tokens, jnp.int32))


def routing(params, tokens, cfg, pad_to=None):
    """The routes of ``forward_tail``: a list, one entry a layer."""
    return forward_tail(params, tokens, cfg, 1, pad_to)[1]
