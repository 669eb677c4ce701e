"""Plain reference for the language model of ``zai-org/GLM-5.2``
(``glm_moe_dsa``): latent attention with a compressed query (MLA,
arXiv:2405.04434 section 2.1.2) over a learned sparse selection of key
positions (DeepSeek-V3.2-Exp's lightning indexer), computed in the layers
whose indexer is ``"full"`` and reused by the ``"shared"`` layers after them,
a leading dense SwiGLU layer, then sigmoid-routed SwiGLU experts with a shared
expert (``noaux_tc``, arXiv:2412.19437 section 2.1.2, one group).
``docs/glm_dsa.md`` has the equations and every ``assumed`` reading.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: MLA unabsorbed (every position's
keys and values expanded for each head), the index scores as a full ``[t, s]``
matrix, the selection by a sort and then a mask over all keys, a loop over the
experts with a mask, no kernels, no cache, no batching, nothing imported from
the program. It reads the program's parameter tree as data: ``embed``,
``head``, ``ln_f.g``, ``blocks[i].{ln1.g, ln2.g}``, ``mla.{wq_a, q_norm.g,
wq_b, wdkv, kv_norm.g, wukv, wo}`` and, in a layer with an indexer,
``mla.indexer.{wq, wk, k_norm.{g, b}, ww}``; ``glu.{w1, w3, w2}`` or
``moe.{router, bias, w_gate, w_up, w_down, shared.{w_gate, w_up, w_down}}``.
A layer without ``mla.indexer`` attends the selection of the last layer that
had one.

The chip's share, as ``reference_ling``: ``cfg["share"]`` = ``{"first_expert":
f, "held": n}`` says that ``moe.w_*`` hold the router's experts ``f .. f + n -
1``; the layer adds the chosen experts that are held and the shared expert.
``share=None`` is the uncut layer.

Departures from the published model, each the configuration file's too:

- memory, not arithmetic: the selection mask ``[T, T]`` is made a block of
  queries at a time, and attention runs a group of heads and a block of queries
  at a time, so that a 17k-token sequence fits beside 10.7 GB of weights;
- every held expert runs on every token and a mask keeps the chosen ones;
- the Hadamard rotation of the indexer's queries and keys is left out (it is
  orthogonal: no dot product changes), as is its fp8 storage; RoPE is on the
  first ``index_rope_dim`` dimensions of both; the key's LayerNorm uses
  ``rms_norm_eps``;
- the multi-token-prediction module is left out.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512          # queries whose [q, T] scores are alive at once
HEAD_GROUP = 8         # heads whose expanded keys and values are alive at once


def _rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def _layernorm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g.astype(F32) + b.astype(F32)


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


def _blocks(t, size):
    """``(n, size)`` with n * size == t: ``size`` itself where it divides t,
    else one block."""
    return (t // size, size) if t > size and t % size == 0 else (1, t)


# ---- the indexer ------------------------------------------------------------
def index_inputs(x, c_q, ip, cfg):
    """x [T, D], c_q [T, rq] -> (q^I [T, hI, dI], k^I [T, dI], w [T, hI])."""
    t = x.shape[0]
    h, d, rd = (cfg["index_n_heads"], cfg["index_head_dim"],
                cfg["index_rope_dim"])
    theta = float(cfg["rope_theta"])
    q = (c_q @ ip["wq"].astype(F32)).reshape(t, h, d)
    q = jnp.concatenate([_rope_interleaved(q[..., :rd], theta), q[..., rd:]],
                        axis=-1)
    k = _layernorm(x @ ip["wk"].astype(F32), ip["k_norm"]["g"],
                   ip["k_norm"]["b"], cfg["rms_norm_eps"])
    k = jnp.concatenate(
        [_rope_interleaved(k[:, None, :rd], theta)[:, 0], k[:, rd:]], axis=-1)
    w = x @ ip["ww"].astype(F32) / math.sqrt(h) / math.sqrt(d)
    return q, k, w


def selection_mask(x, c_q, ip, cfg, selected=None, tail_start=None):
    """The keys each position attends, ``[T, T]`` bool (row t: ``S_t``), and
    how a handed-in selection stands: ``(mask, shortfall [n], wrong [n],
    overlap [n])``.

    Row t is the ``index_topk`` positions s <= t of largest ``I(t, s)``
    (all of them while t < index_topk): a stable sort, best first, whose
    first k positions are set in a mask (equal scores: the lower
    position). ``selected`` [n, k] ints (-1: none) names instead the keys
    of the n rows from ``tail_start`` on, as another
    implementation chose them; ``shortfall`` is then how far the weakest key
    it names falls short of this reference's k-th best for that row, as a
    share of the standard deviation of the row's scores over s <= t (0 for
    a choice as good as the reference's own), and ``wrong`` counts what no
    rounding explains: a position beyond t, one named twice, or fewer keys
    than min(t + 1, k); ``overlap`` is the share of the row's min(t + 1, k)
    keys that it names and this reference's own selection holds too (1 for
    the reference's own choice: a top-k below recall 1 reads below 1 here
    whatever its weakest key scores). A row of -2 names nothing: it keeps
    this reference's own selection and is not judged."""
    t = x.shape[0]
    k = min(int(cfg["index_topk"]), t)
    q, keys, w = index_inputs(x, c_q, ip, cfg)
    n_blocks, size = _blocks(t, Q_BLOCK)

    def scores(q, w, rows):
        s = jnp.einsum("qhd,td->qht", q, keys)
        s = jnp.einsum("qht,qh->qt", jax.nn.relu(s), w)
        s = jnp.where(s == 0, 0.0, s)           # -0.0 is 0.0 (the sort's order)
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        return jnp.where(causal, s, -jnp.inf), causal

    def block(i):
        rows = i * size + jnp.arange(size)
        s, causal = scores(jax.lax.dynamic_slice_in_dim(q, i * size, size),
                           jax.lax.dynamic_slice_in_dim(w, i * size, size),
                           rows)
        # a stable sort, best first: equal scores go to the lower position
        best = jnp.argsort(-s, axis=-1, stable=True)[:, :k]
        picked = jnp.zeros(s.shape, bool).at[
            jnp.arange(size)[:, None], best].set(True)
        return picked & causal

    mask = jax.lax.map(block, jnp.arange(n_blocks)).reshape(t, t)
    if selected is None:
        return (mask, jnp.zeros((0,), F32), jnp.zeros((0,), jnp.int32),
                jnp.zeros((0,), F32))
    n = selected.shape[0]
    rows = tail_start + jnp.arange(n)
    s, causal = scores(jax.lax.dynamic_slice_in_dim(q, tail_start, n),
                       jax.lax.dynamic_slice_in_dim(w, tail_start, n), rows)
    kth = jnp.sort(s, axis=-1)[:, t - k]
    named = selected >= 0
    judged = selected[:, 0] != -2
    theirs = jnp.zeros((n, t), jnp.int32).at[
        jnp.arange(n)[:, None], jnp.where(named, selected, t)].add(
            1, mode="drop")
    count = jnp.sum(causal, axis=-1)
    mean = jnp.sum(jnp.where(causal, s, 0.0), -1) / count
    spread = jnp.sqrt(jnp.sum(jnp.where(causal, (s - mean[:, None]) ** 2,
                                        0.0), -1) / count)
    weakest = jnp.min(jnp.where(theirs > 0, s, jnp.inf), axis=-1)
    shortfall = jnp.maximum(kth - weakest, 0.0) / jnp.maximum(spread, 1e-30)
    # a key beyond t scores -inf: an infinite shortfall; count it apart
    beyond = jnp.sum((theirs > 0) & ~causal, axis=-1)
    wrong = (beyond + jnp.sum(theirs > 1, axis=-1)
             + (jnp.sum(named, axis=-1) != jnp.minimum(count, k)))
    shortfall = jnp.where((beyond > 0) | ~judged, 0.0, shortfall)
    own = jax.lax.dynamic_slice_in_dim(mask, tail_start, n)
    overlap = jnp.sum((theirs > 0) & own, axis=-1) / jnp.minimum(count, k)
    mask = jax.lax.dynamic_update_slice_in_dim(
        mask, jnp.where(judged[:, None], (theirs > 0) & causal, own),
        tail_start, axis=0)
    return (mask, shortfall, jnp.where(judged, wrong, 0).astype(jnp.int32),
            jnp.where(judged, overlap, 1.0).astype(F32))


# ---- latent attention over a selection --------------------------------------
def mla_mixer(x, p, cfg, mask_from, dense=False):
    """The unabsorbed form on the normed x [T, D] -> ``(y [T, D], mask,
    shortfall, wrong, overlap)``. ``mask_from(c_q)`` gives
    ``selection_mask``'s result for this layer (a layer with an indexer
    computes it, the others are handed the last one made); ``dense``
    attends every s <= t instead,
    the control that switches the selection off."""
    t = x.shape[0]
    heads, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    theta = float(cfg["rope_theta"])
    c_q = _rmsnorm(x @ p["wq_a"].astype(F32), p["q_norm"]["g"],
                   cfg["rms_norm_eps"])
    mask, *verdict = mask_from(c_q)
    down = x @ p["wdkv"].astype(F32)
    c = _rmsnorm(down[:, :r], p["kv_norm"]["g"], cfg["rms_norm_eps"])
    k_r = _rope_interleaved(down[:, None, r:], theta)          # [T, 1, dr]
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
        q = jnp.concatenate(
            [q[..., :dn], _rope_interleaved(q[..., dn:], theta)], axis=-1)
        up = jnp.einsum("tr,rhd->thd", c, cut(wukv))
        k = jnp.concatenate(
            [up[..., :dn], jnp.broadcast_to(k_r, (t, group, dr))], axis=-1)
        v = up[..., dn:]

        def block(i):
            ok = jax.lax.dynamic_slice_in_dim(causal if dense else mask,
                                              i * size, size)
            scores = jnp.einsum(
                "qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, i * size, size),
                k) / math.sqrt(dn + dr)
            prob = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", prob, v)

        o = jax.lax.map(block, jnp.arange(n_blocks)).reshape(t, group, dv)
        return y + jnp.einsum(
            "thd,hdm->tm", o,
            jax.lax.dynamic_slice_in_dim(wo, g * group, group, axis=0))

    y = jax.lax.fori_loop(0, n_groups, heads_of, jnp.zeros_like(x))
    return (y, mask, *verdict)


# ---- the router and the experts ---------------------------------------------
def route(x, moe, cfg, chosen=None):
    """x [T, D] -> (weights [T, k], experts [T, k], lead [T], shortfall
    [T]), as ``reference_ling.route`` for one group: the experts are the k
    largest biased sigmoid scores unless ``chosen`` [T, k] names them, the
    weights this router's unbiased scores of them, normalised and scaled;
    ``shortfall`` is how far the least biased score chosen falls short of
    the k-th best, as a share of it."""
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("this reference routes over one group")
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ moe["router"].astype(F32))           # [T, E]
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


# ---- the model --------------------------------------------------------------
def _forward(params, tokens, cfg, chosen=None, selected=None,
             tail_start=None, dense=False):
    """tokens [T] -> (final-normed hidden states [T, D], per expert layer
    ``route``'s routing, per layer with an indexer ``(mask [T, T],
    shortfall, wrong, overlap)``, per layer the mixer's output [T, D])."""
    eps = cfg["rms_norm_eps"]
    h = params["embed"].astype(F32)[tokens]
    routes, picks, mixed = [], [], []
    last = None
    for blk in params["blocks"]:
        x = _rmsnorm(h, blk["ln1"]["g"], eps)
        p = blk["mla"]
        if "indexer" in p:
            sel = None if selected is None else selected[len(picks)]
            y, *last = mla_mixer(
                x, p, cfg, lambda c_q, x=x, p=p, sel=sel: selection_mask(
                    x, c_q, p["indexer"], cfg, sel, tail_start), dense)
            picks.append(tuple(last))
        else:
            y = mla_mixer(x, p, cfg, lambda c_q, last=last: last, dense)[0]
        mixed.append(y)
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
    return _rmsnorm(h, params["ln_f"]["g"], eps), routes, picks, mixed


def _logits(params, hidden):
    return hidden @ params["head"].astype(F32).T


KEYS = ("num_attention_heads", "rms_norm_eps", "rope_theta", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "index_n_heads", "index_head_dim", "index_rope_dim", "index_topk",
        "num_experts_per_tok", "n_group", "topk_group",
        "routed_scaling_factor")


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
def _jit_tail(cfg_key, n_tail, dense):
    cfg = _cfg(cfg_key)

    def f(params, tokens, real_len, chosen, selected):
        start = jnp.maximum(real_len - n_tail, 0)
        hid, routes, picks, _ = _forward(params, tokens, cfg, chosen,
                                         selected, start, dense)
        tail = jax.lax.dynamic_slice_in_dim(hid, start, n_tail)
        return _logits(params, tail), routes, [p[1:] for p in picks]

    return jax.jit(f)


def forward_tail(params, tokens, cfg, n_tail, pad_to=None, chosen=None,
                 selected=None, dense=False):
    """One forward over ``tokens`` ([T] ints) -> ``(logits, routes,
    picks)``: teacher-forced float32 logits at the last ``min(n_tail, T)``
    positions against the whole context; each expert layer's routing of every
    position (``route``); and, for each layer with an indexer, ``(shortfall
    [n], wrong [n], overlap [n])`` of the selection handed in for the last n
    positions (``selection_mask``; empty without one).

    ``chosen`` ([Lmoe, T, k] ints): the experts another implementation chose
    at every position. ``selected`` ([Lfull, n, k] ints, -1: none; n <=
    n_tail): the key positions it selected for the last n positions (the
    tail's rows before them keep the reference's own). The
    reference then computes the model with *those* experts and, at those
    positions, *those* keys (its own selection elsewhere), and says whether
    each choice was admissible. ``dense=True``: no selection at all, every
    query attends every s <= t: the control that must fail the check.

    ``pad_to`` pads the sequence on the right so that few lengths compile:
    causality and the per-token experts make the pad inert."""
    tokens = jnp.asarray(tokens, jnp.int32)
    real = int(tokens.shape[0])
    n_tail = min(int(n_tail), real)
    if chosen is not None:
        chosen = jnp.asarray(chosen, jnp.int32)[:, :real]
    n = 0                               # rows handed in: positions real - n ..
    if selected is not None:
        selected = jnp.asarray(selected, jnp.int32)
        n = selected.shape[1]
        selected = jnp.pad(selected, ((0, 0), (n_tail - n, 0), (0, 0)),
                           constant_values=-2)
    if pad_to is not None and pad_to > real:
        tokens = jnp.pad(tokens, (0, pad_to - real))
        if chosen is not None:
            chosen = jnp.pad(chosen, ((0, 0), (0, pad_to - real), (0, 0)))
    with jax.default_matmul_precision("highest"):
        logits, routes, picks = _jit_tail(_key(cfg), n_tail, bool(dense))(
            params, tokens, jnp.asarray(real, jnp.int32), chosen, selected)
    return (logits[:n_tail],
            [tuple(x[:real] for x in r) for r in routes],
            [tuple(x[n_tail - n:] for x in p) for p in picks])


def forward(params, tokens, cfg, chosen=None, dense=False):
    """The whole forward for a test: ``(logits [T, V], routes, masks, mixed)``
    with ``masks`` the ``[T, T]`` selection of each layer with an indexer
    and ``mixed`` every layer's attention output [T, D]."""
    with jax.default_matmul_precision("highest"):
        hid, routes, picks, mixed = _forward(
            params, jnp.asarray(tokens, jnp.int32), cfg,
            None if chosen is None else jnp.asarray(chosen, jnp.int32),
            dense=dense)
        return (_logits(params, hid), routes, [p[0] for p in picks], mixed)
