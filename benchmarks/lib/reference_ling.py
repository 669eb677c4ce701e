"""Plain reference for the language model of ``inclusionAI/Ling-3.0-flash-VL``
(``bailing_hybrid``): Kimi Delta Attention layers (arXiv:2510.26692 section
3) with a latent-attention layer (MLA, arXiv:2405.04434 section 2.1) closing
every run of ``layer_group_size``, a leading dense SwiGLU layer, then
group-limited sigmoid-routed SwiGLU experts with a shared expert
(``noaux_tc``, arXiv:2412.19437 section 2.1.2). ``docs/ling_hybrid.md`` has
the equations and every ``assumed`` reading of the config's keys.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence one token after
another (no chunks), MLA with every position's keys and values expanded from
its latent (nothing absorbed), a loop over the experts with a mask, no
kernels, no cache, no batching, and nothing imported from the program. It
reads the program's parameter tree as data: ``embed``, ``head``, ``ln_f.g``,
``blocks[i].{ln1.g, ln2.g}`` and, by layer, ``kda.{wq, wk, wv, wa, wb, wg,
wo, conv_q, conv_k, conv_v, a_log, dt_bias, o_norm.g}`` or ``mla.{wq, wdkv,
kv_norm.g, wukv, wo, wg}``, and ``glu.{w1, w3, w2}`` or ``moe.{router, bias,
w_gate, w_up, w_down, shared.{w_gate, w_up, w_down}}``.

The chip's share: ``cfg["share"]`` = ``{"first_expert": f, "held": n}`` says
that ``moe.w_*`` hold the router's experts ``f .. f + n - 1``. The router
keeps all its outputs, its groups and its k a token; the layer adds the
chosen experts that are held and the shared expert, and leaves out what the
others would add. ``share=None`` is the uncut layer: ``moe.w_*`` hold every
expert.

Departures from the published model, each the configuration file's too:

- the unembedding is read as ``head`` [V, D] and applied as ``h head^T``;
- MLA runs in query blocks so that a 9,216-token context fits beside the
  weights: memory, not arithmetic;
- every held expert runs on every token and a mask keeps the chosen ones:
  the same sum, in expert order rather than top-k order;
- a group or an expert outside the kept groups is masked with -inf for the
  choice (the published code fills 0; the two differ only where a biased
  score is negative);
- the vision tower is left out: the catalog row gives no size of one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
L2_EPS = 1e-6


def _rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope_interleaved(x, theta):
    """x: [T, H, d] at positions 0..T-1; pair i is dimensions (2i, 2i+1)."""
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


# ---- Kimi Delta Attention ---------------------------------------------------
def _conv(x, taps):
    """Depthwise causal convolution over time: x [T, C], taps [K, C];
    y_t = sum_j taps[j] x_{t - (K-1) + j}, zeros before the start."""
    width = taps.shape[0]
    t = x.shape[0]
    full = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), F32), x])
    return sum(full[j:j + t] * taps[j].astype(F32) for j in range(width))


def kda_recurrence(q, k, v, g, beta):
    """The recurrence, one position after another. q, k, g [T, H, dk],
    v [T, H, dv], beta [T, H] -> (o [T, H, dv], S [H, dk, dv])."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[:, :, None] * s                      # S'
        u = vt - jnp.einsum("hkv,hk->hv", s, kt)             # v - S'^T k
        s = s + bt[:, None, None] * kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    s, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), F32),
                        (q, k, v, g, beta))
    return o, s


def kda_inputs(x, p, cfg):
    """x [T, D] (the normed input) -> q, k, v, g, beta, gate of the
    recurrence and the output gate."""
    t = x.shape[0]
    heads = cfg["num_attention_heads"]

    def branch(w, taps):
        y = jax.nn.silu(_conv(x @ p[w].astype(F32), p[taps]))
        return y.reshape(t, heads, -1)

    q, k, v = (branch("wq", "conv_q"), branch("wk", "conv_k"),
               branch("wv", "conv_v"))
    dk = q.shape[-1]
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
        * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    a = (x @ p["wa"].astype(F32) + p["dt_bias"].astype(F32)).reshape(
        t, heads, dk)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["a_log"].astype(F32))[:, None] * a)
    beta = jax.nn.sigmoid(x @ p["wb"].astype(F32))
    gate = jax.nn.sigmoid(x @ p["wg"].astype(F32))
    return q, k, v, g, beta, gate


def kda_mixer(x, p, cfg):
    """The whole mixer on the normed x [T, D] -> (y [T, D], S)."""
    q, k, v, g, beta, gate = kda_inputs(x, p, cfg)
    o, s = kda_recurrence(q, k, v, g, beta)
    o = _rmsnorm(o, p["o_norm"]["g"], L2_EPS) * gate[..., None]
    return o.reshape(x.shape[0], -1) @ p["wo"].astype(F32), s


# ---- latent attention -------------------------------------------------------
def mla_mixer(x, p, cfg, q_block=1024):
    """The unabsorbed form on the normed x [T, D] -> y [T, D]."""
    t = x.shape[0]
    heads, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    theta = float(cfg["rope_theta"])
    q = (x @ p["wq"].astype(F32)).reshape(t, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope_interleaved(q[..., dn:], theta)],
                        axis=-1)
    down = x @ p["wdkv"].astype(F32)
    c = _rmsnorm(down[:, :r], p["kv_norm"]["g"], cfg["rms_norm_eps"])
    k_r = _rope_interleaved(down[:, None, r:], theta)          # [T, 1, dr]
    up = (c @ p["wukv"].astype(F32)).reshape(t, heads, dn + dv)
    k = jnp.concatenate([up[..., :dn],
                         jnp.broadcast_to(k_r, (t, heads, dr))], axis=-1)
    v = up[..., dn:]
    outs = []
    for s in range(0, t, q_block):
        e = min(t, s + q_block)
        scores = jnp.einsum("qhd,khd->hqk", q[s:e], k[:e]) \
            / math.sqrt(dn + dr)
        ok = jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None]
        prob = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", prob, v[:e]))
    o = jnp.concatenate(outs, axis=0)
    gate = jax.nn.sigmoid(x @ p["wg"].astype(F32))
    return (o * gate[..., None]).reshape(t, -1) @ p["wo"].astype(F32)


# ---- the router and the experts ---------------------------------------------
def route(x, moe, cfg, chosen=None):
    """x [T, D] -> (weights [T, k], experts [T, k], lead [T], shortfall
    [T]). The experts are the reference's own choice, largest biased score
    first, unless ``chosen`` [T, k] names them; the weights are this
    router's unbiased scores of those experts, normalised and scaled.

    ``lead``: how far the k-th biased score chosen leads the next one among
    the kept groups, as a share of it. ``shortfall`` judges a ``chosen``
    set (0 for the reference's own): the larger of (a) how far the worst
    group a chosen expert lies in falls short of the ``topk_group``-th best
    group score, and (b) how far the least biased score chosen falls short
    of the k-th best among the groups that choice keeps (its own groups,
    filled up with the reference's best), each as a share of the score it
    is held to. A router fed rounded activations may exchange groups or
    experts that close, and nothing else."""
    k = cfg["num_experts_per_tok"]
    n_group, topk_group = cfg["n_group"], cfg["topk_group"]
    t = x.shape[0]
    s = jax.nn.sigmoid(x @ moe["router"].astype(F32))           # [T, E]
    biased = s + moe["bias"].astype(F32)
    grouped = biased.reshape(t, n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    top_groups = jax.lax.top_k(group_score, topk_group)[0]
    gth = top_groups[:, -1]

    def kept_scores(prefer):
        """Biased scores with every group outside the ``topk_group`` best
        of ``prefer`` [T, n_group] at -inf."""
        best = jax.lax.top_k(prefer, topk_group)[1]
        kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=1)
        return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(t, -1)

    own = kept_scores(group_score)
    top, experts = jax.lax.top_k(own, k + 1)
    lead = (top[:, k - 1] - top[:, k]) / jnp.abs(top[:, k - 1])
    shortfall = jnp.zeros((t,), F32)
    if chosen is not None:
        experts = chosen
        size = grouped.shape[-1]
        in_group = jnp.any((chosen // size)[..., None]
                           == jnp.arange(n_group), axis=1)      # [T, n_group]
        worst = jnp.min(jnp.where(in_group, group_score, jnp.inf), axis=-1)
        by_group = jnp.maximum(gth - worst, 0.0) / jnp.abs(gth)
        theirs = kept_scores(jnp.where(in_group, jnp.inf, group_score))
        kth = jax.lax.top_k(theirs, k)[0][:, -1]
        least = jnp.min(jnp.take_along_axis(biased, chosen, axis=-1), -1)
        shortfall = jnp.maximum(by_group,
                                jnp.maximum(kth - least, 0.0) / jnp.abs(kth))
    experts = experts[:, :k]
    w = jnp.take_along_axis(s, experts, axis=-1)
    w = cfg["routed_scaling_factor"] * w / jnp.sum(w, axis=-1, keepdims=True)
    return w, experts, lead, shortfall


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def routed_part(x, w, e, moe, first):
    """One held expert after another on every token; a token keeps the
    result of an expert it chose, times that expert's weight. ``moe.w_*``
    hold the router's experts ``first ..``."""
    def one(i, out):
        wi = jnp.sum(jnp.where(e == first + i, w, 0.0), -1, keepdims=True)
        return out + wi * _swiglu(x, moe["w_gate"][i], moe["w_up"][i],
                                  moe["w_down"][i])

    return jax.lax.fori_loop(0, moe["w_gate"].shape[0], one,
                             jnp.zeros_like(x))


def expert_layer(x, moe, cfg, chosen=None):
    """The routed feed-forward on the normed x [T, D]: this share's routed
    part plus the shared expert -> (y [T, D], route(...))."""
    r = route(x, moe, cfg, chosen)
    share = cfg.get("share")
    first = share["first_expert"] if share else 0
    sh = moe["shared"]
    y = routed_part(x, r[0], r[1], moe, first) + _swiglu(
        x, sh["w_gate"], sh["w_up"], sh["w_down"])
    return y, r


# ---- the model --------------------------------------------------------------
def _forward(params, tokens, cfg, chosen=None):
    """tokens [T] -> (final-normed hidden states [T, D], per expert layer
    the routing of ``route``, per KDA layer its final state). ``chosen``
    [Lmoe, T, k] makes every expert layer use those experts."""
    eps = cfg["rms_norm_eps"]
    h = params["embed"].astype(F32)[tokens]
    routes, states = [], []
    for blk in params["blocks"]:
        x = _rmsnorm(h, blk["ln1"]["g"], eps)
        if "kda" in blk:
            y, s = kda_mixer(x, blk["kda"], cfg)
            states.append(s)
        else:
            y = mla_mixer(x, blk["mla"], cfg)
        h = h + y
        x = _rmsnorm(h, blk["ln2"]["g"], eps)
        if "moe" in blk:
            y, r = expert_layer(
                x, blk["moe"], cfg,
                None if chosen is None else chosen[len(routes)])
            routes.append(r)
        else:
            g = blk["glu"]
            y = _swiglu(x, g["w1"], g["w3"], g["w2"])
        h = h + y
    return _rmsnorm(h, params["ln_f"]["g"], eps), routes, states


def _logits(params, hidden):
    return hidden @ params["head"].astype(F32).T


def _key(cfg):
    keep = ("num_attention_heads", "rms_norm_eps", "rope_theta",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "n_group", "topk_group",
            "routed_scaling_factor", "kda_lower_bound")
    share = cfg.get("share")
    return tuple((k, cfg[k]) for k in keep) + (
        ("share", share and (share["first_expert"], share["held"])),)


def _cfg(cfg_key):
    cfg = dict(cfg_key)
    if cfg["share"]:
        cfg["share"] = {"first_expert": cfg["share"][0],
                        "held": cfg["share"][1]}
    return cfg


@functools.lru_cache(maxsize=None)
def _jit_tail(cfg_key, n_tail):
    cfg = _cfg(cfg_key)

    def f(params, tokens, real_len, chosen):
        hid, routes, _ = _forward(params, tokens, cfg, chosen)
        start = jnp.maximum(real_len - n_tail, 0)
        tail = jax.lax.dynamic_slice_in_dim(hid, start, n_tail)
        return _logits(params, tail), routes

    return jax.jit(f)


def forward_tail(params, tokens, cfg, n_tail, pad_to=None, chosen=None):
    """One forward over ``tokens`` ([T] ints) -> ``(logits, routes)``:
    teacher-forced float32 logits at the last ``min(n_tail, T)`` positions
    against the whole context, and each expert layer's routing of every
    position as ``(weights [T, k], experts [T, k], lead [T], shortfall
    [T])`` (``route``). With ``chosen`` ([Lmoe, T, k] ints: the experts
    another implementation chose) the reference computes the model with
    *those* experts, weighted by its own scores of them, and ``shortfall``
    says whether the choice was admissible.

    ``pad_to`` pads the sequence on the right so that few lengths compile:
    causality, the recurrence's order and the per-token experts make the
    pad inert for the positions before it."""
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


def final_states(params, tokens, cfg):
    """Every KDA layer's state ``[H, dk, dv]`` after ``tokens`` ([T])."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32), cfg)[2]
