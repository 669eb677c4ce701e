"""Plain reference for the language model of ``zai-org/GLM-5.3-Flash``
(``glm5_next_text``): a residual of four streams mixed by
manifold-constrained hyper-connections (mHC, arXiv:2512.24880) round every
sub-layer; Kimi Delta Attention layers (arXiv:2510.26692 section 3, with
Kimi Linear's low-rank decay gate and channel-wise output gate) three to one
with a latent-attention layer without a rotary part (MLA with a compressed
query, arXiv:2405.04434 section 2.1.2, ``qk_rope_head_dim`` 0) whose
lightning indexer scores **pooled** index keys; leading dense SwiGLU layers,
then sigmoid-routed SwiGLU experts with a shared expert (``noaux_tc``, one
group), every gate and up projection clamped by ``swiglu_limit``.
``docs/glm53_flash.md`` has the equations and every ``assumed`` reading.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence one token after
another, Sinkhorn as ``hc_sinkhorn_iters`` written sweeps over ``[T, n, n]``,
the pooled scores as a dense ``[T, T / pool]`` matrix, the selection by a sort
and then a mask over all keys, MLA unabsorbed, a loop over the experts with a
mask; no kernels, nothing imported from the program. It reads the program's parameter tree as data: ``embed``, ``head``,
``ln_f.g``, ``blocks[i].{ln1.g, ln2.g, hc1, hc2}`` with ``hc*.{phi [n D, 2 n
+ n n] (columns pre, post, res), alpha [3], b [2 n + n n]}``, then ``kda.{wq,
wk, wv, wa_down, wa_up, wb, wg_down, wg_up, wo, conv_q, conv_k, conv_v, a_log,
dt_bias, o_norm.g}`` or ``mla.{wq_a, q_norm.g, wq_b, wdkv, kv_norm.g, wukv,
wo, indexer.{wq, wk, k_norm.{g, b}, ww}}``, and ``glu.{w1, w3, w2}`` or
``moe.{router, bias, w_gate, w_up, w_down, shared.{w_gate, w_up, w_down}}``.

The chip's share, as ``reference_glm_dsa``: ``cfg["share"]`` = ``{"first_expert":
f, "held": n}``; ``share=None`` is the uncut layer.

Departures from the published model, each the configuration file's too:

- memory, not arithmetic: attention runs a block of queries and a group of
  heads at a time, and ``forward_tail`` takes a long sequence ``BLOCK``
  positions at a time through every layer, each block from what the blocks
  before left (``kda_state``, ``mla_state``: the same recurrence and the same
  keys, computed in the same order), so that a 59k-token sequence fits
  beside 8 GB of weights; ``forward`` takes a sequence whole;
- every held expert runs on every token and a mask keeps the chosen ones;
- the multi-token-prediction module and the vision tower are left out.

Two switches are the controls that a check must fail: ``plain=True`` replaces
every map by the plain residual's (``H_res = I``, ``H_pre = 1 / n``, ``H_post
= 1``: the streams stay copies of one plain residual), ``recent=True`` replaces
the selection by the most recent ``index_topk`` positions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
L2_EPS = 1e-6
BLOCK = 2048           # positions ``forward_tail`` takes through the layers at once
Q_BLOCK = 512          # queries whose [q, T] scores are alive at once
HEAD_GROUP = 8         # heads whose expanded keys and values are alive at once


def _rmsnorm(x, g, eps):
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if g is None else y * g.astype(F32)


def _layernorm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g.astype(F32) + b.astype(F32)


def _rope_interleaved(x, theta, start=0):
    """x: [T, H, d] at positions start..start+T-1; pair i is dimensions (2i,
    2i+1)."""
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = (start + jnp.arange(t)).astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def _blocks(t, size):
    """``(n, size)`` with n * size == t: ``size`` itself where it divides t,
    else one block."""
    return (t // size, size) if t > size and t % size == 0 else (1, t)


# ---- hyper-connections -------------------------------------------------------
def sinkhorn(m, iters, eps):
    """``iters`` written sweeps over ``m`` [T, n, n] (row i, column j):
    divide every row by its sum, then every column by its."""
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def hc_maps(xs, p, cfg, plain=False):
    """xs [T, n, D] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    t, n, d = xs.shape
    if plain:
        return (jnp.full((t, n), 1.0 / n, F32), jnp.ones((t, n), F32),
                jnp.broadcast_to(jnp.eye(n, dtype=F32), (t, n, n)))
    flat = _rmsnorm(xs.reshape(t, n * d), None, cfg["rms_norm_eps"])
    phi, alpha, b = (p[k].astype(F32) for k in ("phi", "alpha", "b"))
    pre = jax.nn.sigmoid(alpha[0] * (flat @ phi[:, :n]) + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * (flat @ phi[:, n:2 * n])
                                + b[n:2 * n])
    m0 = jnp.exp(alpha[2] * (flat @ phi[:, 2 * n:]) + b[2 * n:])
    res = sinkhorn(m0.reshape(t, n, n), cfg["hc_sinkhorn_iters"],
                   cfg["hc_eps"])
    return pre, post, res


def hc_sublayer(xs, p, cfg, f, plain=False):
    """``X' = H_res X + H_post^T F(H_pre X)`` on xs [T, n, D]; ``f`` maps the
    sub-layer's input [T, D] to ``(y [T, D], whatever else it leaves)``."""
    pre, post, res = hc_maps(xs, p, cfg, plain)
    y, left = f(jnp.einsum("tn,tnd->td", pre, xs))
    return (jnp.einsum("tij,tjd->tid", res, xs)
            + post[:, :, None] * y[:, None, :]), left


# ---- Kimi Delta Attention ----------------------------------------------------
def _conv(x, taps, before):
    """Depthwise causal convolution over time: x [T, C], taps [K, C],
    ``before`` [K - 1, C] the rows that came before x (zeros at a sequence's
    start) -> (y [T, C], the last K - 1 rows seen)."""
    t = x.shape[0]
    full = jnp.concatenate([before, x])
    y = sum(full[j:j + t] * taps[j].astype(F32)
            for j in range(taps.shape[0]))
    return y, full[t:]


def kda_recurrence(q, k, v, g, beta, s0):
    """One position after another from the matrix ``s0`` [H, dk, dv]. q, k,
    g [T, H, dk], v [T, H, dv], beta [T, H] -> (o [T, H, dv], S)."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[:, :, None] * s
        u = vt - jnp.einsum("hkv,hk->hv", s, kt)
        s = s + bt[:, None, None] * kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def kda_state(p, cfg):
    """A KDA layer's state before the first position: the recurrent matrix
    and the convolutions' rows before, all zeros."""
    heads, taps = cfg["kda_heads"], p["conv_q"].shape[0]
    c = p["wq"].shape[1]
    dk = c // heads
    before = jnp.zeros((taps - 1, c), F32)
    return jnp.zeros((heads, dk, dk), F32), before, before, before


def kda_mixer(x, p, cfg, state=None):
    """The mixer on the normed x [T, D] -> (y [T, D], what x leaves, in
    ``kda_state``'s form: the matrix first): the low-rank decay gate f = (x
    Wa_down) Wa_up and the channel-wise output gate sigmoid((x Wg_down)
    Wg_up). ``state``: what the positions before x left (None: nothing)."""
    t = x.shape[0]
    heads = cfg["kda_heads"]
    s0, *before = kda_state(p, cfg) if state is None else state

    def branch(w, taps, rows):
        y, rows = _conv(x @ p[w].astype(F32), p[taps], rows)
        return jax.nn.silu(y).reshape(t, heads, -1), rows

    (q, bq), (k, bk), (v, bv) = (
        branch(w, taps, rows) for w, taps, rows in zip(
            ("wq", "wk", "wv"), ("conv_q", "conv_k", "conv_v"), before))
    dk = q.shape[-1]
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    f = (x @ p["wa_down"].astype(F32)) @ p["wa_up"].astype(F32)
    a = (f + p["dt_bias"].astype(F32)).reshape(t, heads, dk)
    g = cfg["gate_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["a_log"].astype(F32))[:, None] * a)
    beta = jax.nn.sigmoid(x @ p["wb"].astype(F32))
    gate = jax.nn.sigmoid((x @ p["wg_down"].astype(F32))
                          @ p["wg_up"].astype(F32)).reshape(t, heads, -1)
    o, s = kda_recurrence(q, k, v, g, beta, s0)
    o = _rmsnorm(o, p["o_norm"]["g"], L2_EPS) * gate
    return o.reshape(t, -1) @ p["wo"].astype(F32), (s, bq, bk, bv)


# ---- the pooled indexer -------------------------------------------------------
def index_inputs(x, c_q, ip, cfg, start=0):
    """x [T, D], c_q [T, rq] at positions start.. -> (q^I [T, hI, dI], k^I
    [T, dI], w [T, hI])."""
    t = x.shape[0]
    h, d, rd = (cfg["index_n_heads"], cfg["index_head_dim"],
                cfg["index_rope_dim"])
    theta = float(cfg["index_rope_theta"])
    q = (c_q @ ip["wq"].astype(F32)).reshape(t, h, d)
    q = jnp.concatenate([_rope_interleaved(q[..., :rd], theta, start),
                         q[..., rd:]], axis=-1)
    k = _layernorm(x @ ip["wk"].astype(F32), ip["k_norm"]["g"],
                   ip["k_norm"]["b"], cfg["rms_norm_eps"])
    k = jnp.concatenate(
        [_rope_interleaved(k[:, None, :rd], theta, start)[:, 0], k[:, rd:]],
        axis=-1)
    w = x @ ip["ww"].astype(F32) / math.sqrt(h) / math.sqrt(d)
    return q, k, w


def pooled_scores(q, w, keys, pool, q_pos):
    """The dense matrix ``I(t, p)`` [Q, ceil(T / pool)] of the queries at
    positions ``q_pos`` [Q] against every pool's mean key (``keys`` [T, dI]
    from position 0; a last, short pool is padded with zeros and never
    scored), -inf where the pool does not end before the query's own, and
    that bound as a mask."""
    t, d = keys.shape
    n = -(-t // pool)
    means = jnp.mean(jnp.pad(keys, ((0, n * pool - t), (0, 0))).reshape(
        n, pool, d), axis=1)
    s = jnp.einsum("qhd,pd->qhp", q, means)
    s = jnp.einsum("qhp,qh->qp", jax.nn.relu(s), w)
    s = jnp.where(s == 0, 0.0, s)           # -0.0 is 0.0 (the sort's order)
    ended = jnp.arange(n)[None, :] < (q_pos // pool)[:, None]
    return jnp.where(ended, s, -jnp.inf), ended


def select(q, w, keys, q_pos, cfg, selected=None, recent=False):
    """The keys each of the queries at positions ``q_pos`` [Q] attends among
    ``keys`` [T, dI] (the index keys from position 0 on, the queries' own
    among them), ``[Q, T]`` bool, and how a handed-in selection stands:
    ``(mask, shortfall [Q], wrong [Q], overlap [Q])``.

    The query at t: the ``index_topk / index_kpool`` pools p < P(t) = t //
    pool of largest ``I(t, p)`` (a stable sort, best first: equal scores to
    the lower pool; all of them while there are no more), each pool's
    positions, and the tail ``pool P(t) .. t``. ``selected`` [Q, k] ints (-1:
    none) names instead the keys of each query, as another implementation
    chose them; a row of -2 keeps the reference's own selection and is not
    judged. ``wrong`` counts what no rounding explains: a position beyond t
    or named twice, a pool named in part, a tail position missing, or
    another number of pools than min(P(t), k); ``shortfall`` is how far the
    weakest pool it names falls short of this reference's k-th best for that
    query, in standard deviations of the query's scores over the pools it
    may choose from; ``overlap`` the share of the query's pools that this
    reference's own selection holds too. ``recent``: the control, every
    query attends its ``index_topk`` most recent positions."""
    n, t = q_pos.shape[0], keys.shape[0]
    pool = int(cfg["index_kpool"])
    k = int(cfg["index_topk"]) // pool
    at = jnp.arange(t)
    causal = at[None, :] <= q_pos[:, None]
    none = (jnp.zeros((n,), F32), jnp.zeros((n,), jnp.int32),
            jnp.ones((n,), F32))
    if recent:
        return (causal & (at[None, :] > q_pos[:, None]
                          - int(cfg["index_topk"])), *none)
    s, ended = pooled_scores(q, w, keys, pool, q_pos)       # [Q, n_pools]
    n_pools = s.shape[1]
    kk = min(k, n_pools)
    best = jnp.argsort(-s, axis=-1, stable=True)[:, :kk]
    own = jnp.zeros(s.shape, bool).at[jnp.arange(n)[:, None], best].set(
        True) & ended
    tail = causal & (at[None, :] >= (q_pos // pool * pool)[:, None])
    mask = jnp.repeat(own, pool, axis=-1)[:, :t] | tail
    if selected is None:
        return (mask, *none)
    named = selected >= 0
    judged = selected[:, 0] != -2
    theirs = jnp.zeros((n, t), jnp.int32).at[
        jnp.arange(n)[:, None], jnp.where(named, selected, t)].add(
            1, mode="drop")
    held = theirs > 0
    # the pools they name: by the positions named outside the tail
    in_pool = jnp.pad(held & ~tail, ((0, 0), (0, n_pools * pool - t))
                      ).reshape(n, n_pools, pool).sum(-1)
    pools = in_pool > 0
    count = jnp.sum(ended, axis=-1)                         # P(t)
    want = jnp.minimum(count, kk)
    wrong = (jnp.sum(held & ~causal, axis=-1) + jnp.sum(theirs > 1, axis=-1)
             + jnp.sum(pools & (in_pool != pool), axis=-1)
             + jnp.sum(pools & ~ended, axis=-1)
             + jnp.sum(tail & ~held, axis=-1)
             + (jnp.sum(pools, axis=-1) != want))
    mean = jnp.sum(jnp.where(ended, s, 0.0), -1) / jnp.maximum(count, 1)
    spread = jnp.sqrt(jnp.sum(jnp.where(
        ended, (s - mean[:, None]) ** 2, 0.0), -1) / jnp.maximum(count, 1))
    kth = jnp.sort(s, axis=-1)[:, n_pools - kk]
    weakest = jnp.min(jnp.where(pools & ended, s, jnp.inf), axis=-1)
    shortfall = jnp.where(
        jnp.isfinite(weakest) & jnp.isfinite(kth),
        jnp.maximum(kth - weakest, 0.0) / jnp.maximum(spread, 1e-30), 0.0)
    overlap = jnp.where(want > 0, jnp.sum(pools & own, axis=-1)
                        / jnp.maximum(want, 1), 1.0)
    return (jnp.where(judged[:, None], held & causal, mask),
            jnp.where(judged, shortfall, 0.0),
            jnp.where(judged, wrong, 0).astype(jnp.int32),
            jnp.where(judged, overlap, 1.0).astype(F32))


# ---- latent attention without a rotary part ------------------------------------
def mla_state(p, cfg, length):
    """A latent layer's rows before the first position, for ``length``
    positions: the cached rows c and the index keys, all zeros."""
    return (jnp.zeros((length, cfg["kv_lora_rank"]), F32),
            jnp.zeros((length, cfg["index_head_dim"]), F32))


def mla_mixer(x, p, cfg, selected=None, recent=False, state=None, start=0):
    """The unabsorbed form on the normed x [T, D] at positions ``start``.. ->
    ``(y [T, D], (mask, shortfall, wrong, overlap), the rows)``: c_q =
    rmsnorm(x Wqa), q_h = c_q Wqb (H x dn, no rotary part), c = rmsnorm(x
    Wdkv) (the whole cached row), [k_h ; v_h] = c Wukv, scale dn^-1/2, over
    the layer's own selection (``select``; ``selected`` [T, k] as there).
    ``state`` (``mla_state``'s form) holds the rows of the positions before
    x, and the keys are its rows with x's own written at ``start``; None:
    x is the whole sequence and its own rows are the keys."""
    t = x.shape[0]
    heads, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    c_q = _rmsnorm(x @ p["wq_a"].astype(F32), p["q_norm"]["g"], eps)
    c = _rmsnorm(x @ p["wdkv"].astype(F32), p["kv_norm"]["g"], eps)
    iq, keys, iw = index_inputs(x, c_q, p["indexer"], cfg, start)
    if state is not None:
        c, keys = (jax.lax.dynamic_update_slice_in_dim(old, new, start, 0)
                   for old, new in zip(state, (c, keys)))
    mask, *verdict = select(iq, iw, keys, start + jnp.arange(t), cfg,
                            selected, recent)
    wq_b = p["wq_b"].astype(F32).reshape(-1, heads, dn)
    wukv = p["wukv"].astype(F32).reshape(r, heads, dn + dv)
    wo = p["wo"].astype(F32).reshape(heads, dv, -1)
    n_blocks, size = _blocks(t, Q_BLOCK)
    n_groups, group = _blocks(heads, HEAD_GROUP)

    def heads_of(g, y):
        def cut(w):
            return jax.lax.dynamic_slice_in_dim(w, g * group, group, axis=1)

        q = jnp.einsum("tc,chd->thd", c_q, cut(wq_b))
        up = jnp.einsum("tr,rhd->thd", c, cut(wukv))
        k, v = up[..., :dn], up[..., dn:]

        def block(i):
            ok = jax.lax.dynamic_slice_in_dim(mask, i * size, size)
            scores = jnp.einsum(
                "qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, i * size, size),
                k) / math.sqrt(dn)
            prob = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", prob, v)

        o = jax.lax.map(block, jnp.arange(n_blocks)).reshape(t, group, dv)
        return y + jnp.einsum(
            "thd,hdm->tm", o,
            jax.lax.dynamic_slice_in_dim(wo, g * group, group, axis=0))

    y = jax.lax.fori_loop(0, n_groups, heads_of, jnp.zeros_like(x))
    return y, (mask, *verdict), (c, keys)


# ---- the feed-forwards --------------------------------------------------------
def route(x, moe, cfg, chosen=None):
    """x [T, D] -> (weights [T, k], experts [T, k], lead [T], shortfall
    [T]), as ``reference_glm_dsa.route``: one group, the k largest biased
    sigmoid scores unless ``chosen`` names them."""
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("this reference routes over one group")
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ moe["router"].astype(F32))
    biased = s + moe["bias"].astype(F32)
    top, experts = jax.lax.top_k(biased, k + 1)
    lead = (top[:, k - 1] - top[:, k]) / jnp.abs(top[:, k - 1])
    shortfall = jnp.zeros((x.shape[0],), F32)
    if chosen is not None:
        experts = chosen
        least = jnp.min(jnp.take_along_axis(biased, chosen, axis=-1), -1)
        kth = top[:, k - 1]
        shortfall = jnp.maximum(kth - least, 0.0) / jnp.abs(kth)
    experts = experts[:, :k]
    w = jnp.take_along_axis(s, experts, axis=-1)
    w = cfg["routed_scaling_factor"] * w / jnp.sum(w, axis=-1, keepdims=True)
    return w, experts, lead, shortfall


def balanced_bias(x, moe, cfg, passes, step, decay):
    """The expert bias as ``noaux_tc`` leaves it in training (DeepSeek-V3,
    arXiv:2412.19437 section 2.1.2), on the tokens x [T, D]: ``passes``
    times, every expert that received more than the mean load has its bias
    lowered by ``step * decay ** pass`` and every one that received less
    raised. The scores are those of x; only the choice moves."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ moe["router"].astype(F32))

    def one(i, bias):
        _, experts = jax.lax.top_k(s + bias, k)
        load = jnp.zeros(bias.shape, F32).at[experts.reshape(-1)].add(1.0)
        return bias + step * decay ** i.astype(F32) * jnp.sign(
            jnp.mean(load) - load)

    return jax.lax.fori_loop(0, passes, one, moe["bias"].astype(F32))


def _swiglu(x, w_gate, w_up, w_down, limit):
    """``swiglu_limit``: the gate clamped to at most ``limit`` and the up
    projection to [-limit, limit] before their product (0: no clamp)."""
    gate, up = x @ w_gate.astype(F32), x @ w_up.astype(F32)
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return (jax.nn.silu(gate) * up) @ w_down.astype(F32)


def expert_layer(x, moe, cfg, chosen=None):
    """This share's routed part plus the shared expert -> (y, route(...))."""
    r = route(x, moe, cfg, chosen)
    share = cfg.get("share")
    first = share["first_expert"] if share else 0
    limit = cfg["swiglu_limit"]

    def one(i, out):
        wi = jnp.sum(jnp.where(r[1] == first + i, r[0], 0.0), -1,
                     keepdims=True)
        return out + wi * _swiglu(x, moe["w_gate"][i], moe["w_up"][i],
                                  moe["w_down"][i], limit)

    y = jax.lax.fori_loop(0, moe["w_gate"].shape[0], one, jnp.zeros_like(x))
    sh = moe["shared"]
    return y + _swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"], limit), r


# ---- the model ------------------------------------------------------------------
def _forward(params, tokens, cfg, chosen=None, selected=None, plain=False,
             recent=False, balance=None, state=None, start=0):
    """tokens [T] at positions ``start``.. -> (final-normed hidden states [T,
    D], per expert layer ``route``'s routing, per latent layer ``(mask,
    shortfall, wrong, overlap)`` of ``select``, per layer what it leaves
    (``kda_state``'s or ``mla_state``'s form), the streams [T, n, D], per
    expert layer the bias it routed by). ``state``: what the positions
    before left, a layer an entry; None: nothing, a sequence's start.
    ``balance`` = ``(passes, step, decay)``: every expert layer routes by
    ``balanced_bias`` of its own input, so a later layer is balanced on what
    the balanced earlier ones hand it."""
    eps, n = cfg["rms_norm_eps"], int(cfg["hc_mult"])
    e = params["embed"].astype(F32)[tokens]
    xs = jnp.broadcast_to(e[:, None, :], (e.shape[0], n, e.shape[1]))
    routes, picks, left, biases = [], [], [], []

    def experts(u, blk):
        x, moe = _rmsnorm(u, blk["ln2"]["g"], eps), blk["moe"]
        if balance is not None:
            moe = {**moe, "bias": balanced_bias(x, moe, cfg, *balance)}
        biases.append(moe["bias"])
        return expert_layer(x, moe, cfg,
                            None if chosen is None else chosen[len(routes)])

    for i, blk in enumerate(params["blocks"]):
        before = None if state is None else state[i]
        if "kda" in blk:
            xs, after = hc_sublayer(
                xs, blk["hc1"], cfg, lambda u, blk=blk, before=before:
                kda_mixer(_rmsnorm(u, blk["ln1"]["g"], eps), blk["kda"], cfg,
                          before), plain)
        else:
            sel = None if selected is None else selected[len(picks)]

            def latent(u, blk=blk, sel=sel, before=before):
                y, verdict, after = mla_mixer(
                    _rmsnorm(u, blk["ln1"]["g"], eps), blk["mla"], cfg, sel,
                    recent, before, start)
                return y, (verdict, after)

            xs, (verdict, after) = hc_sublayer(xs, blk["hc1"], cfg, latent,
                                               plain)
            picks.append(verdict)
        left.append(after)
        if "moe" in blk:
            xs, r = hc_sublayer(xs, blk["hc2"], cfg,
                                lambda u, blk=blk: experts(u, blk), plain)
            routes.append(r)
        else:
            g = blk["glu"]
            xs, _ = hc_sublayer(
                xs, blk["hc2"], cfg, lambda u, blk=blk, g=g: (_swiglu(
                    _rmsnorm(u, blk["ln2"]["g"], eps), g["w1"], g["w3"],
                    g["w2"], cfg["swiglu_limit"]), None), plain)
    hid = _rmsnorm(jnp.sum(xs, axis=1), params["ln_f"]["g"], eps)
    return hid, routes, picks, left, xs, biases


def _logits(params, hidden):
    return hidden @ params["head"].astype(F32).T


KEYS = ("num_attention_heads", "kda_heads", "rms_norm_eps", "gate_lower_bound",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "v_head_dim",
        "index_n_heads", "index_head_dim", "index_rope_dim",
        "index_rope_theta", "index_topk", "index_kpool",
        "num_experts_per_tok", "n_group", "topk_group",
        "routed_scaling_factor", "swiglu_limit", "hc_mult",
        "hc_sinkhorn_iters", "hc_eps")


def _key(cfg):
    share = cfg.get("share")
    return tuple((k, cfg[k]) for k in KEYS) + (
        ("share", share and (share["first_expert"], share["held"])),)


def _cfg(cfg_key):
    cfg = dict(cfg_key)
    if cfg["share"]:
        cfg["share"] = {"first_expert": cfg["share"][0],
                        "held": cfg["share"][1]}
    return cfg


@functools.lru_cache(maxsize=None)
def _jit_block(cfg_key, plain, recent):
    cfg = _cfg(cfg_key)

    def f(params, tokens, start, state, chosen, selected):
        hid, routes, picks, state, _, _ = _forward(
            params, tokens, cfg, chosen, selected, plain, recent,
            state=state, start=start)
        return hid, routes, [p[1:] for p in picks], state

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _jit_start(cfg_key, length):
    cfg = _cfg(cfg_key)
    return jax.jit(lambda params: [
        kda_state(blk["kda"], cfg) if "kda" in blk
        else mla_state(blk["mla"], cfg, length) for blk in params["blocks"]])


def forward_tail(params, tokens, cfg, n_tail, pad_to=None, chosen=None,
                 selected=None, plain=False, recent=False):
    """One forward over ``tokens`` ([T] ints) -> ``(logits, routes,
    picks)``, as ``reference_glm_dsa.forward_tail``: teacher-forced float32
    logits at the last ``min(n_tail, T)`` positions; each expert layer's
    routing of every position; for each latent layer ``(shortfall [n], wrong
    [n], overlap [n])`` of the selection handed in for the last n positions.

    ``chosen`` ([Lmoe, T, k] ints): the experts another implementation chose
    at every position. ``selected`` ([Lmla, n, k] ints, -1: none): the key
    positions it selected for the last n positions. ``plain`` and
    ``recent``: the controls (the module's docstring).

    ``pad_to`` positions (default T) are held: where that is a multiple of
    ``BLOCK`` the sequence is taken ``BLOCK`` positions at a time, each
    block through every layer from what the blocks before left (the
    recurrent matrices and the convolutions' last rows; the latent rows and
    the index keys of all ``pad_to`` positions, those still to come zeros
    that causality hides), so that what is alive at once does not grow with
    the sequence; else in one block. The sequence is padded on the right to
    whole blocks: causality, the recurrence's order and the per-token
    experts make the pad inert."""
    tokens = np.asarray(tokens, np.int32)
    real = int(tokens.shape[0])
    n_tail = min(int(n_tail), real)
    _, size = _blocks(int(pad_to or real), BLOCK)
    blocks = -(-real // size)
    if blocks * size > int(pad_to or real):
        raise ValueError(f"{real} positions do not fit pad_to={pad_to}")
    held = blocks * size
    tokens = np.pad(tokens, (0, held - real))
    if chosen is not None:
        chosen = np.pad(np.asarray(chosen, np.int32)[:, :real],
                        ((0, 0), (0, held - real), (0, 0)))
    n = 0
    if selected is not None:
        selected = np.asarray(selected, np.int32)
        n = selected.shape[1]
        rows = np.full((selected.shape[0], held, selected.shape[2]), -2,
                       np.int32)
        rows[:, real - n:real] = selected
        selected = rows
    key = _key(cfg)
    hid, routes, picks = [], [], []
    with jax.default_matmul_precision("highest"):
        state = _jit_start(key, int(pad_to or real))(params)
        for i in range(blocks):
            cut = slice(i * size, (i + 1) * size)
            h, r, p, state = _jit_block(key, bool(plain), bool(recent))(
                params, tokens[cut], np.int32(i * size), state,
                None if chosen is None else chosen[:, cut],
                None if selected is None else selected[:, cut])
            hid.append(np.asarray(h))
            routes.append(jax.tree_util.tree_map(np.asarray, r))
            picks.append(jax.tree_util.tree_map(np.asarray, p))
        del state
        logits = _jit_logits()(
            params, np.concatenate(hid)[real - n_tail:real])

    def joined(parts):      # per layer a tuple of arrays over all positions
        return [tuple(np.concatenate(x) for x in zip(*layer))
                for layer in zip(*parts)]

    return (logits,
            [tuple(x[:real] for x in r) for r in joined(routes)],
            [tuple(x[real - n:real] for x in p) for p in joined(picks)])


@functools.lru_cache(maxsize=None)
def _jit_logits():
    return jax.jit(_logits)


def forward(params, tokens, cfg, chosen=None, plain=False, recent=False):
    """The whole forward for a test, in one block: ``(logits [T, V],
    routes, masks, states, streams)``: ``masks`` the ``[T, T]`` selection of
    each latent layer, ``states`` every KDA layer's final ``[H, dk, dv]``,
    ``streams`` the residual [T, n, D] after the last layer."""
    with jax.default_matmul_precision("highest"):
        hid, routes, picks, left, xs, _ = _forward(
            params, jnp.asarray(tokens, jnp.int32), cfg,
            None if chosen is None else jnp.asarray(chosen, jnp.int32),
            plain=plain, recent=recent)
        states = [after[0] for blk, after in zip(params["blocks"], left)
                  if "kda" in blk]
        return (_logits(params, hid), routes, [p[0] for p in picks], states,
                xs)


@functools.lru_cache(maxsize=None)
def _jit_balance(cfg_key, balance):
    cfg = _cfg(cfg_key)

    def f(params, tokens):
        _, routes, _, _, _, biases = _forward(params, tokens, cfg,
                                              balance=balance)
        every = jnp.arange(biases[0].shape[0])
        load = jnp.stack([jnp.sum(r[1][..., None] == every, axis=(0, 1))
                          for r in routes]).astype(F32)
        return jnp.stack(biases), jnp.max(
            load / jnp.mean(load, axis=1, keepdims=True))

    return jax.jit(f)


def balanced_biases(params, tokens, cfg, passes, step, decay):
    """One forward of this reference over ``tokens`` ([T] ints) in which
    every expert layer's bias is balanced on its own input
    (``balanced_bias``) -> ``(biases [Lmoe, E], the largest expert's load
    over the mean at the balanced bias, on these tokens)``. The bias is
    made by this file alone: a program under test has no part in it."""
    with jax.default_matmul_precision("highest"):
        return _jit_balance(_key(cfg), (int(passes), float(step),
                                        float(decay)))(
            params, jnp.asarray(tokens, jnp.int32))
