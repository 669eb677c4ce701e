"""Plain reference for ``ai-sage/GigaChat3.1-702B-A36B`` (``deepseek_v3``):
latent attention with a compressed query (MLA, DeepSeek-V2 arXiv:2405.04434
section 2.1) whose rotary part is scaled by YaRN (arXiv:2309.00071, as the
``deepseek_v3`` modelling code applies it), leading dense SwiGLU layers, then
group-limited sigmoid routing (``noaux_tc``, DeepSeek-V3 arXiv:2412.19437
section 2.1.2) over SwiGLU experts with a shared expert, and the model's
multi-token-prediction module of depth 1 (section 2.2) with its loss term.
``docs/gigachat_mtp.md`` has the equations and every ``assumed`` reading.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: MLA unabsorbed (every position's
keys and values expanded for each head), the YaRN frequencies from the
formulas written out here, a loop over the experts with a mask, no kernels, no
cache, no batching, nothing imported from the program. It reads the program's
parameter tree as data: ``embed``, ``head``, ``ln_f.g``, ``blocks[i].{ln1.g,
ln2.g}``, ``mla.{wq_a, q_norm.g, wq_b, wdkv, kv_norm.g, wukv, wo}``,
``glu.{w1, w3, w2}`` or ``moe.{router, bias, w_gate, w_up, w_down,
shared.{w_gate, w_up, w_down}}``, and ``mtp.{enorm.g, hnorm.g, proj, block,
norm.g}`` with ``block`` one more layer's tree.

The chip's share, as ``reference_ling``: ``cfg["share"]`` = ``{"first_expert":
f, "held": n}`` says that ``moe.w_*`` hold the router's experts ``f .. f + n -
1``; the layer adds the chosen experts that are held and the shared expert.
``share=None`` is the uncut layer.

Departures from the published description, each the configuration file's too:

- memory, not arithmetic: attention runs a group of heads and a block of
  queries at a time;
- every held expert runs on every token and a mask keeps the chosen ones;
- the module's input is ``[rmsnorm_e(Emb(t_{i+1})) ; rmsnorm_h(g_i)] M``, the
  embedding first as the released serving code has it (the paper writes the
  hidden state first: with seeded weights a relabelling of ``M``'s rows), and
  ``g_i`` is the model's hidden state *after* its final norm (eq. 21 leaves
  that open; released serving code hands on the normed state);
- ``rope_interleave``: pair i of the rotary part is dimensions (2i, 2i + 1);
- ``mscale`` = ``mscale_all_dim``: cos and sin stay unscaled and the softmax
  scale is ``(dn + dr)^-1/2 (0.1 mscale_all_dim ln(factor) + 1)^2``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512          # queries whose [q, T] scores are alive at once
HEAD_GROUP = 8         # heads whose expanded keys and values are alive at once


def _rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


# ---- YaRN -------------------------------------------------------------------
def yarn_frequencies(d, theta, rs):
    """The d / 2 rotary frequencies of a ``d``-wide rotary part, float64:
    ``f_i = theta^(-2i/d)``; with ``rs`` (``rope_scaling``, yarn)

        corr(n) = d ln(L / (2 pi n)) / (2 ln theta)      L = original length
        low  = max(floor(corr(beta_fast)), 0)
        high = min(ceil(corr(beta_slow)), d - 1)
        ramp_i = clip((i - low) / (high - low), 0, 1)    i = 0 .. d/2 - 1
        f'_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i"""
    i = np.arange(d // 2, dtype=np.float64)
    f = np.power(float(theta), -2.0 * i / d)
    if not rs:
        return f
    length = rs["original_max_position_embeddings"]
    corr = [d * math.log(length / (2 * math.pi * turns))
            / (2 * math.log(theta))
            for turns in (rs["beta_fast"], rs["beta_slow"])]
    low, high = max(math.floor(corr[0]), 0), min(math.ceil(corr[1]), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + f / rs["factor"] * ramp


def softmax_scale(cfg):
    """``(dn + dr)^-1/2``, times ``(0.1 mscale_all_dim ln(factor) + 1)^2``
    under YaRN."""
    scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim") and rs["factor"] > 1:
        scale *= (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1) ** 2
    return scale


def _rope(x, cfg, positions=None):
    """x: [T, H, d] at ``positions`` (default 0..T-1); pair i is dimensions
    (2i, 2i+1) (``rope_interleave``) or (i, i + d/2)."""
    t, _, d = x.shape
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale", 1) != rs.get("mscale_all_dim", 0):
        raise ValueError("this reference takes mscale == mscale_all_dim: cos "
                         "and sin unscaled")
    inv = jnp.asarray(yarn_frequencies(d, cfg["rope_theta"], rs), F32)
    pos = jnp.arange(t, dtype=F32) if positions is None else positions
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if cfg["rope_interleave"]:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _blocks(t, size):
    """``(n, size)`` with n * size == t: ``size`` itself where it divides t,
    else one block."""
    return (t // size, size) if t > size and t % size == 0 else (1, t)


# ---- latent attention, unabsorbed -------------------------------------------
def mla_mixer(x, p, cfg):
    """The normed x [T, D] -> y [T, D]: causal attention with every
    position's keys and values expanded from its latent, for each head."""
    t = x.shape[0]
    heads, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, scale = cfg["rms_norm_eps"], softmax_scale(cfg)
    c_q = _rmsnorm(x @ p["wq_a"].astype(F32), p["q_norm"]["g"], eps)
    down = x @ p["wdkv"].astype(F32)
    c = _rmsnorm(down[:, :r], p["kv_norm"]["g"], eps)
    k_r = _rope(down[:, None, r:], cfg)                        # [T, 1, dr]
    wq_b = p["wq_b"].astype(F32).reshape(-1, heads, dn + dr)
    wukv = p["wukv"].astype(F32).reshape(r, heads, dn + dv)
    wo = p["wo"].astype(F32).reshape(heads, dv, -1)
    n_blocks, size = _blocks(t, Q_BLOCK)
    n_groups, group = _blocks(heads, HEAD_GROUP)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def heads_of(g, y):
        def cut(w):
            return jax.lax.dynamic_slice_in_dim(w, g * group, group, axis=1)

        q = jnp.einsum("tc,chd->thd", c_q, cut(wq_b))
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg)], axis=-1)
        up = jnp.einsum("tr,rhd->thd", c, cut(wukv))
        k = jnp.concatenate(
            [up[..., :dn], jnp.broadcast_to(k_r, (t, group, dr))], axis=-1)
        v = up[..., dn:]

        def block(i):
            ok = jax.lax.dynamic_slice_in_dim(causal, i * size, size)
            scores = jnp.einsum(
                "qhd,khd->hqk",
                jax.lax.dynamic_slice_in_dim(q, i * size, size), k) * scale
            prob = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", prob, v)

        o = jax.lax.map(block, jnp.arange(n_blocks)).reshape(t, group, dv)
        return y + jnp.einsum(
            "thd,hdm->tm", o,
            jax.lax.dynamic_slice_in_dim(wo, g * group, group, axis=0))

    return jax.lax.fori_loop(0, n_groups, heads_of, jnp.zeros_like(x))


# ---- the router and the experts ---------------------------------------------
def route(x, moe, cfg, chosen=None):
    """x [T, D] -> (weights [T, k], experts [T, k], lead [T], shortfall
    [T]). Scores ``s = sigmoid(x Wr)``; a bias is added for choosing only;
    the experts lie in ``n_group`` runs, a group's score is the sum of its
    two largest biased scores, the ``topk_group`` best groups stay, and the k
    largest biased scores among their experts are chosen, unless ``chosen``
    [T, k] names the experts; the weights are the chosen experts' unbiased
    scores, normalised to 1 and times ``routed_scaling_factor``.

    ``shortfall`` judges a ``chosen`` set (0 for the reference's own): the
    larger of how far the worst group a chosen expert lies in falls short of
    the ``topk_group``-th best group score, and how far the least biased
    score chosen falls short of the k-th best among the groups that choice
    keeps (its own, filled up with the reference's best), each as a share of
    the score it is held to."""
    k = cfg["num_experts_per_tok"]
    n_group, topk_group = cfg["n_group"], cfg["topk_group"]
    t = x.shape[0]
    s = jax.nn.sigmoid(x @ moe["router"].astype(F32))           # [T, E]
    biased = s + moe["bias"].astype(F32)
    grouped = biased.reshape(t, n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    gth = jax.lax.top_k(group_score, topk_group)[0][:, -1]

    def kept_scores(prefer):
        best = jax.lax.top_k(prefer, topk_group)[1]
        kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=1)
        return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(t, -1)

    top, experts = jax.lax.top_k(kept_scores(group_score), k + 1)
    lead = (top[:, k - 1] - top[:, k]) / jnp.abs(top[:, k - 1])
    shortfall = jnp.zeros((t,), F32)
    if chosen is not None:
        experts = chosen
        in_group = jnp.any((chosen // grouped.shape[-1])[..., None]
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
    result of an expert it chose, times that expert's weight."""
    def one(i, out):
        wi = jnp.sum(jnp.where(e == first + i, w, 0.0), -1, keepdims=True)
        return out + wi * _swiglu(x, moe["w_gate"][i], moe["w_up"][i],
                                  moe["w_down"][i])

    return jax.lax.fori_loop(0, moe["w_gate"].shape[0], one,
                             jnp.zeros_like(x))


def expert_layer(x, moe, cfg, chosen=None):
    """This share's routed part plus the shared expert -> (y, route(...))."""
    r = route(x, moe, cfg, chosen)
    share = cfg.get("share")
    first = share["first_expert"] if share else 0
    sh = moe["shared"]
    y = routed_part(x, r[0], r[1], moe, first) + _swiglu(
        x, sh["w_gate"], sh["w_up"], sh["w_down"])
    return y, r


# ---- the model and its module -----------------------------------------------
def _layer(h, blk, cfg, chosen=None):
    """One pre-norm layer on h [T, D] -> (h, routing or None)."""
    eps = cfg["rms_norm_eps"]
    h = h + mla_mixer(_rmsnorm(h, blk["ln1"]["g"], eps), blk["mla"], cfg)
    x = _rmsnorm(h, blk["ln2"]["g"], eps)
    if "moe" in blk:
        y, r = expert_layer(x, blk["moe"], cfg, chosen)
        return h + y, r
    g = blk["glu"]
    return h + _swiglu(x, g["w1"], g["w3"], g["w2"]), None


def _forward(params, tokens, cfg, chosen=None, module_off=False, after=None):
    """tokens [T] -> (g [T, D], the model's final-normed hidden states;
    m [T, D] or None, the module's output after its own norm, row i made from
    ``g_i`` and ``after[i]`` = token i + 1 (default: the tokens rolled by
    one, so the last row reads token 0 and means nothing); per expert layer
    ``route``'s routing, the module's last). ``chosen`` [Lmoe (+ 1), T, k] makes every expert layer use those
    experts. ``module_off``: the control, the module's hidden-state input
    zeroed."""
    eps = cfg["rms_norm_eps"]
    embed = params["embed"].astype(F32)
    h = embed[tokens]
    routes = []
    for blk in params["blocks"]:
        h, r = _layer(h, blk, cfg,
                      None if chosen is None or "moe" not in blk
                      else chosen[len(routes)])
        if r is not None:
            routes.append(r)
    g = _rmsnorm(h, params["ln_f"]["g"], eps)
    m = None
    if "mtp" in params:
        p = params["mtp"]
        both = jnp.concatenate(
            [_rmsnorm(embed[jnp.roll(tokens, -1) if after is None else after],
                      p["enorm"]["g"], eps),
             jnp.zeros_like(g) if module_off
             else _rmsnorm(g, p["hnorm"]["g"], eps)], axis=-1)
        hm, r = _layer(both @ p["proj"].astype(F32), p["block"], cfg,
                       None if chosen is None or "moe" not in p["block"]
                       else chosen[len(routes)])
        if r is not None:
            routes.append(r)
        m = _rmsnorm(hm, p["norm"]["g"], eps)
    return g, m, routes


def _logits(params, hidden):
    return hidden @ params["head"].astype(F32).T


KEYS = ("num_attention_heads", "rms_norm_eps", "rope_theta", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "num_experts_per_tok", "n_group", "topk_group",
        "routed_scaling_factor", "rope_interleave")


def _key(cfg):
    share, rs = cfg.get("share"), cfg.get("rope_scaling")
    return tuple((k, cfg[k]) for k in KEYS) + (
        ("share", share and (share["first_expert"], share["held"])),
        ("rope_scaling", rs and tuple(sorted(rs.items()))))


def _cfg(cfg_key):
    cfg = dict(cfg_key)
    if cfg["share"]:
        cfg["share"] = {"first_expert": cfg["share"][0],
                        "held": cfg["share"][1]}
    if cfg["rope_scaling"]:
        cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@functools.lru_cache(maxsize=None)
def _jit_tail(cfg_key, n_tail, module_off):
    cfg = _cfg(cfg_key)

    def f(params, tokens, real_len, chosen, after):
        start = jnp.maximum(real_len - n_tail, 0)
        g, m, routes = _forward(params, tokens, cfg, chosen, module_off,
                                after)

        def tail(hidden):
            return _logits(params, jax.lax.dynamic_slice_in_dim(
                hidden, start, n_tail))

        return tail(g), None if m is None else tail(m), routes

    return jax.jit(f)


def _after(tokens, real, after):
    """The tokens one position on: ``tokens`` rolled by one, with ``after``
    (the token that follows the ``real`` ones; None: none does) in the last
    real place."""
    nxt = jnp.roll(tokens, -1)
    return nxt if after is None else nxt.at[real - 1].set(after)


def forward_tail(params, tokens, cfg, n_tail, pad_to=None, chosen=None,
                 module_off=False, after=None):
    """One forward over ``tokens`` ([T] ints) -> ``(logits, module_logits,
    routes)``: teacher-forced float32 logits at the last ``n = min(n_tail,
    T)`` positions against the whole context, the model's (row j: the
    distribution of the token after position T - n + j) and the module's (row
    j: of the token two after it, from the hidden state there and the token
    one after it; the last row reads ``after``, the token that follows the
    sequence, and means nothing without one; None for
    parameters without a module); and each expert layer's routing of every
    position (``route``), the module's last.

    ``chosen`` ([Lmoe + 1, T, k] ints): the experts another implementation
    chose at every position, the module's layer last (its row i belongs to
    position i as the module sees it: hidden state i, token i + 1). The
    reference then computes the model with *those* experts and says whether
    each choice was admissible. ``module_off=True``: the module's
    hidden-state input zeroed, the control that must fail the check.

    ``pad_to`` pads the sequence on the right so that few lengths compile:
    causality and the per-token experts make the pad inert."""
    tokens = jnp.asarray(tokens, jnp.int32)
    real = int(tokens.shape[0])
    n_tail = min(int(n_tail), real)
    if chosen is not None:
        chosen = jnp.asarray(chosen, jnp.int32)[:, :real]
    if pad_to is not None and pad_to > real:
        tokens = jnp.pad(tokens, (0, pad_to - real))
        if chosen is not None:
            chosen = jnp.pad(chosen, ((0, 0), (0, pad_to - real), (0, 0)))
    with jax.default_matmul_precision("highest"):
        logits, extra, routes = _jit_tail(
            _key(cfg), n_tail, bool(module_off))(
                params, tokens, jnp.asarray(real, jnp.int32), chosen,
                _after(tokens, real, after))
    return (logits[:n_tail], None if extra is None else extra[:n_tail],
            [tuple(x[:real] for x in r) for r in routes])


def forward(params, tokens, cfg, chosen=None, module_off=False, after=None):
    """The whole forward for a test: ``(logits [T, V], module_logits [T, V]
    or None, routes)``."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        g, m, routes = _forward(
            params, tokens, cfg,
            None if chosen is None else jnp.asarray(chosen, jnp.int32),
            module_off, _after(tokens, tokens.shape[0], after))
        return (_logits(params, g),
                None if m is None else _logits(params, m), routes)


def loss(params, tokens, cfg, weight):
    """``CE(logits_i, t_{i+1})`` averaged over i, plus ``weight`` times
    ``CE(module's logits_i, t_{i+2})`` averaged over i (DeepSeek-V3 eq.
    24-25, depth 1) for one sequence ``tokens`` [T]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    logits, extra, _ = forward(params, tokens, cfg)

    def ce(lg, targets):
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.mean(logp[jnp.arange(targets.shape[0]), targets])

    return ce(logits[:-1], tokens[1:]) + weight * ce(extra[:-2], tokens[2:])
