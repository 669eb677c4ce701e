"""Plain reference for ``Qwen/Qwen3-Next-80B-A3B-Instruct`` (``qwen3_next``):
three Gated DeltaNet layers (arXiv:2412.06464) then one gated softmax
attention layer in every period of ``full_attention_interval``, and in every
layer softmax-routed SwiGLU experts beside one shared expert behind a
sigmoid gate. With ``x`` the block's normed input:

    Gated DeltaNet (Hk key heads, Hv value heads, dk = dv, K taps)
      [q~ ; k~ ; v~ ; z] = x W_qkvz          [b ; a] = x W_ba
      [q ; k ; v] = silu(conv([q~ ; k~ ; v~]))    depthwise, causal, no bias
      q, k = q / ||q||, k / ||k|| per head;  q = q dk^-1/2;
      key head j serves value heads j Hv/Hk .. (j + 1) Hv/Hk - 1
      beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
      S' = exp(g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t;  y = concat_h(rmsnorm_w(o_t) silu(z_t,h)) W_o

    gated attention (H query heads, Hkv key/value heads of dh)
      [q ; gate] = x W_q (per head: dh of query, dh of gate)
      q, k = rmsnorm(q), rmsnorm(k) per head;  RoPE on the first
      ``rotary_dim`` dimensions of a head, pairs (i, i + rotary_dim / 2)
      o = softmax(q k^T dh^-1/2, causal) v, H / Hkv queries a kv head
      y = (concat_h(o) * sigmoid(gate)) W_o

    experts
      p = softmax(x W_r); the k largest, renormalised to sum 1
      y = sum_j p_j E_j(x) + sigmoid(x . w_sg) E_shared(x)

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence one position
after another (``lax.scan`` over positions IS the recurrence: no chunks, no
WY form), attention as a masked softmax, one held expert after another with
a mask, no kernels, no cache, no batching, and nothing imported from the
program. It reads the program's parameter tree as data: ``embed``, ``head``,
``ln_f.g``, ``blocks[i].{ln1.g, ln2.g}`` and, by layer, ``gdn.{w_qkvz, w_ba,
conv, a_log, dt_bias, o_norm.g, wo}`` or ``attn.{wq, wk, wv, wo, q_norm.g,
k_norm.g}``, and ``moe.{router, w_gate, w_up, w_down, shared.{w_gate, w_up,
w_down, gate}}``.

The chip's share: ``cfg["share"]`` = ``{"first_expert": f, "held": n}`` says
that ``moe.w_*`` hold the router's experts ``f .. f + n - 1``. The router
keeps all its outputs and its k a token; the layer adds the chosen experts
that are held and the gated shared expert, and leaves out what the others
would add. ``share=None`` is the uncut layer: ``moe.w_*`` hold every expert.

Departures from the published model, each the configuration file's too:

- a norm's gain is read as stored, ``g``: the published ``1 + w`` with
  ``g = 1 + w`` (block norms, final norm, q/k norms); the Gated DeltaNet
  output norm's gain is the published plain ``w``;
- ``W_qkvz``'s columns are q, k, v, z in four runs and ``W_ba``'s b then a
  (the published matrix interleaves them by key head): a relabelling of
  columns under seeded weights;
- the unembedding is read as ``head`` [V, D] and applied as ``h head^T``;
- attention runs in blocks of queries so that an 18,432-token context fits
  beside the weights: memory, not arithmetic;
- every held expert runs on every token and a mask keeps the chosen ones:
  the same sum, in expert order rather than top-k order;
- the multi-token-prediction module is left out: the catalog row gives no
  key for it.

Controls (``benchmarks/tools/float8_reference_gdn.py``): ``no_decay`` puts the
scalar gate ``g`` at 0 (a state that never forgets) and ``no_gate`` leaves the
attention's output gate out; a check that passes either does not see the
mechanism.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
L2_EPS = 1e-6
KEYS = ("rms_norm_eps", "rope_theta", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rotary_dim",
        "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "num_experts_per_tok")


def _rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope_partial(x, theta, rotary):
    """x: [T, H, d] at positions 0..T-1; the first ``rotary`` dimensions
    turn, pair i is dimensions (i, i + rotary / 2)."""
    t = x.shape[0]
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rotary:]], axis=-1)


# ---- Gated DeltaNet ---------------------------------------------------------
def _conv(x, taps):
    """Depthwise causal convolution over time: x [T, C], taps [K, C];
    y_t = sum_j taps[j] x_{t - (K-1) + j}, zeros before the start."""
    width = taps.shape[0]
    t = x.shape[0]
    full = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), F32), x])
    return sum(full[j:j + t] * taps[j].astype(F32) for j in range(width))


def gdn_recurrence(q, k, v, g, beta, state=None):
    """The recurrence, one position after another. q, k [T, H, dk], v
    [T, H, dv], g, beta [T, H] -> (o [T, H, dv], S [H, dk, dv])."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[:, None, None] * s                   # S'
        u = vt - jnp.einsum("hkv,hk->hv", s, kt)             # v - S'^T k
        s = s + bt[:, None, None] * kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    s0 = jnp.zeros((h, dk, dv), F32) if state is None else state
    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def gdn_inputs(x, p, cfg):
    """x [T, D] (the normed input) -> q, k, v, g, beta of the recurrence
    (q, k already at the value heads' count) and the output gate z."""
    t = x.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk = cfg["linear_key_head_dim"]
    ck, cv = hk * dk, hv * dk
    proj = x @ p["w_qkvz"].astype(F32)
    mixed = jax.nn.silu(_conv(proj[:, :2 * ck + cv], p["conv"]))
    z = proj[:, 2 * ck + cv:].reshape(t, hv, dk)
    q = mixed[:, :ck].reshape(t, hk, dk)
    k = mixed[:, ck:2 * ck].reshape(t, hk, dk)
    v = mixed[:, 2 * ck:].reshape(t, hv, dk)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
        * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    ba = x @ p["w_ba"].astype(F32)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["a_log"].astype(F32)) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(F32))
    if cfg.get("control") == "no_decay":
        g = jnp.zeros_like(g)
    return q, k, v, g, beta, z


def gdn_mixer(x, p, cfg):
    """The whole mixer on the normed x [T, D] -> (y [T, D], S)."""
    q, k, v, g, beta, z = gdn_inputs(x, p, cfg)
    o, s = gdn_recurrence(q, k, v, g, beta)
    o = _rmsnorm(o, p["o_norm"]["g"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return o.reshape(x.shape[0], -1) @ p["wo"].astype(F32), s


# ---- gated attention --------------------------------------------------------
def attn_mixer(x, p, cfg, q_block=1024):
    """The gated softmax attention on the normed x [T, D] -> y [T, D]."""
    t = x.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    theta, eps = float(cfg["rope_theta"]), cfg["rms_norm_eps"]
    qg = (x @ p["wq"].astype(F32)).reshape(t, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = (x @ p["wk"].astype(F32)).reshape(t, hkv, dh)
    v = (x @ p["wv"].astype(F32)).reshape(t, hkv, dh)
    q = _rope_partial(_rmsnorm(q, p["q_norm"]["g"], eps), theta,
                      cfg["rotary_dim"])
    k = _rope_partial(_rmsnorm(k, p["k_norm"]["g"], eps), theta,
                      cfg["rotary_dim"])
    q = q.reshape(t, hkv, h // hkv, dh)   # query head j reads kv head j // rep
    outs = []
    for s in range(0, t, q_block):
        e = min(t, s + q_block)
        scores = jnp.einsum("qgrd,kgd->grqk", q[s:e], k[:e]) / math.sqrt(dh)
        ok = jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None]
        prob = jax.nn.softmax(jnp.where(ok[None, None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("grqk,kgd->qgrd", prob, v[:e]))
    o = jnp.concatenate(outs, axis=0).reshape(t, h, dh)
    if cfg.get("control") != "no_gate":
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(t, -1) @ p["wo"].astype(F32)


# ---- the router and the experts ---------------------------------------------
def route(x, moe, cfg, chosen=None):
    """x [T, D] -> (weights [T, k], experts [T, k], lead [T], shortfall
    [T]). The experts are the reference's own choice, largest probability
    first, unless ``chosen`` [T, k] names them; the weights are this
    router's probabilities of those experts, renormalised to sum 1.

    ``lead``: how far the k-th probability leads the next one, as a share
    of it. ``shortfall`` judges a ``chosen`` set (0 for the reference's
    own): how far the least probability chosen falls short of the
    reference's k-th, as a share of it. A router fed rounded activations
    may exchange experts that close, and nothing else."""
    k = cfg["num_experts_per_tok"]
    p = jax.nn.softmax(x @ moe["router"].astype(F32), axis=-1)
    top, experts = jax.lax.top_k(p, k + 1)
    lead = (top[:, k - 1] - top[:, k]) / top[:, k - 1]
    shortfall = jnp.zeros((x.shape[0],), F32)
    experts = experts[:, :k]
    if chosen is not None:
        experts = chosen
        least = jnp.min(jnp.take_along_axis(p, chosen, axis=-1), -1)
        shortfall = jnp.maximum(top[:, k - 1] - least, 0.0) / top[:, k - 1]
    w = jnp.take_along_axis(p, experts, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True), experts, lead, shortfall


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


def shared_part(x, moe):
    """The shared expert behind its gate: sigmoid(x . w_sg) E_shared(x)."""
    sh = moe["shared"]
    return jax.nn.sigmoid(x @ sh["gate"].astype(F32))[:, None] * _swiglu(
        x, sh["w_gate"], sh["w_up"], sh["w_down"])


def expert_layer(x, moe, cfg, chosen=None):
    """The routed feed-forward on the normed x [T, D]: this share's routed
    part plus the gated shared expert -> (y [T, D], route(...))."""
    r = route(x, moe, cfg, chosen)
    share = cfg.get("share")
    first = share["first_expert"] if share else 0
    return routed_part(x, r[0], r[1], moe, first) + shared_part(x, moe), r


# ---- the model --------------------------------------------------------------
def _forward(params, tokens, cfg, chosen=None):
    """tokens [T] -> (final-normed hidden states [T, D], per expert layer
    the routing of ``route``, per Gated DeltaNet layer its final state).
    ``chosen`` [Lmoe, T, k] makes every expert layer use those experts."""
    eps = cfg["rms_norm_eps"]
    h = params["embed"].astype(F32)[tokens]
    routes, states = [], []
    for blk in params["blocks"]:
        x = _rmsnorm(h, blk["ln1"]["g"], eps)
        if "gdn" in blk:
            y, s = gdn_mixer(x, blk["gdn"], cfg)
            states.append(s)
        else:
            y = attn_mixer(x, blk["attn"], cfg)
        h = h + y
        x = _rmsnorm(h, blk["ln2"]["g"], eps)
        y, r = expert_layer(x, blk["moe"], cfg,
                            None if chosen is None else chosen[len(routes)])
        routes.append(r)
        h = h + y
    return _rmsnorm(h, params["ln_f"]["g"], eps), routes, states


def _logits(params, hidden):
    return hidden @ params["head"].astype(F32).T


def _key(cfg):
    share = cfg.get("share")
    return tuple((k, cfg[k]) for k in KEYS) + (
        ("share", share and (share["first_expert"], share["held"])),
        ("control", cfg.get("control")))


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
    *those* experts, weighted by its own probabilities of them, and
    ``shortfall`` says whether the choice was admissible.

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
    """Every Gated DeltaNet layer's state ``[Hv, dk, dv]`` after ``tokens``
    ([T])."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32), cfg)[2]
