"""Plain reference for ``JetBrains/Mellum2-12B-A2.5B-Instruct`` (``mellum``):
sliding-window attention layers 3 : 1 with full-attention layers
(``layer_types``), a RoPE per layer kind (``rope_parameters``), and in every
layer 8 of 64 softmax-routed SwiGLU experts. For layer ``i`` of kind
``layer_types[i]`` and its input ``x`` [T, D]:

    a = rmsnorm(x; g1, eps)                       plain gain
    q = a W_q -> H heads of dh;  k = a W_k, v = a W_v -> Hkv heads of dh
        (H dh = 4,096 is not D = 2,304; no bias)
    q, k = rmsnorm(q), rmsnorm(k) per head over dh, a gain a dimension
        (*assumed*: the family's convention; the config has no key for it)
    RoPE over all dh dimensions, pairs (j, j + dh / 2), by the layer's kind:
      sliding_attention  inv_j = theta^(-2j / dh), amplitude 1
      full_attention     YaRN (arXiv:2309.00071, NTK-by-parts):
        corr(n) = dh ln(L0 / (2 pi n)) / (2 ln theta)
        low, high = floor(corr(beta_fast)), ceil(corr(beta_slow)) in [0, dh-1]
        ramp_j = clip((j - low) / (high - low), 0, 1)
        inv'_j = inv_j (1 - ramp_j) + inv_j / factor ramp_j
        and cos, sin times ``attention_factor`` (0.1 ln factor + 1)
    scores q.k / sqrt(dh), causal; a sliding_attention layer's query at t
      sees keys t - (window - 1) .. t (``sliding_window`` keys, its own
      among them), a full_attention layer's 0 .. t; softmax in float32
    h = x + concat_h(o) W_o
    b = rmsnorm(h; g2, eps)
    p = softmax(b W_r) over E experts; the k largest, renormalised to sum 1
    y = sum_j p_j W_d_j (silu(b W_g_j) * b W_u_j);   out = h + y

then the final rmsnorm and the untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: attention as a masked softmax,
one expert after another with a mask, its own RoPE tables, no kernels, no
cache, no batching, and nothing imported from the program. It reads the
program's parameter tree as data: ``embed``, ``head``, ``ln_f.g``,
``blocks[i].{ln1.g, ln2.g, attn.{wq, wk, wv, wo, q_norm.g, k_norm.g},
moe.{router, w_gate, w_up, w_down}}``.

Departures from the published model, each the configuration file's too:

- the q/k head norm is assumed (above);
- the unembedding is read as ``head`` [V, D] and applied as ``h head^T``;
- attention runs in blocks of queries, each against the keys it can see, so
  that a 16,384-token sequence fits beside the weights: memory, not
  arithmetic;
- every expert runs on every token and a mask keeps the chosen ones: the
  same sum, in expert order rather than top-k order;
- the multi-token-prediction head ``described_as`` mentions is left out: the
  catalog row gives no key, width or depth for it.

Controls (``benchmarks/tools/float8_reference_mellum2.py``), each a reference
that computes another model, which a check that sees the mechanism fails:
``no_window`` (a sliding layer sees its whole prefix), ``one_rope`` (a full
layer turns with the sliding layers' table), ``stale_ring`` (a sliding layer
sees ``t - (2 window - 1) .. t``: what a ring that masks one lap late would
compute).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
KEYS = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
        "head_dim", "sliding_window", "num_experts_per_tok", "layer_types",
        "rope_parameters")


def _rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(F32)


def rope_table(dh: int, rope: dict):
    """``(inverse frequencies [dh / 2] float32, amplitude)`` of one section
    of ``rope_parameters``: plain RoPE, or YaRN's by-parts frequencies and
    its ``attention_factor``. Python floats until the last step."""
    half = dh // 2
    theta = float(rope["rope_theta"])
    inv = [theta ** (-j / half) for j in range(half)]
    if rope.get("rope_type", "default") == "default":
        return jnp.asarray(inv, F32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not written")
    length = rope["original_max_position_embeddings"]

    def corr(turns):
        return dh * math.log(length / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), dh - 1)
    out = []
    for j, f in enumerate(inv):
        ramp = min(max((j - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f * (1 - ramp) + f / rope["factor"] * ramp)
    return jnp.asarray(out, F32), float(rope.get(
        "attention_factor", 0.1 * math.log(rope["factor"]) + 1.0))


def _rope(x, inv, amp):
    """x [T, H, dh] at positions 0..T-1; pair j is dimensions (j, j + dh/2)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * amp, jnp.sin(ang)[:, None, :] * amp
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_view(kind: str, cfg):
    """``(rope section, keys a query sees or None for all)`` of a layer of
    ``kind``, as the configuration says, or as a control says instead."""
    control = cfg.get("control")
    rope = cfg["rope_parameters"][
        "sliding_attention" if control == "one_rope" else kind]
    if kind == "full_attention" or control == "no_window":
        return rope, None
    window = cfg["sliding_window"]
    return rope, 2 * window if control == "stale_ring" else window


def attn_mixer(x, p, kind, cfg, q_block=512):
    """The attention of a layer of ``kind`` on the normed x [T, D] -> [T, D].
    A block of queries meets the keys from its window's start to its end."""
    t = x.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    rope, window = layer_view(kind, cfg)
    inv, amp = rope_table(dh, rope)
    q = (x @ p["wq"].astype(F32)).reshape(t, h, dh)
    k = (x @ p["wk"].astype(F32)).reshape(t, hkv, dh)
    v = (x @ p["wv"].astype(F32)).reshape(t, hkv, dh)
    q = _rope(_rmsnorm(q, p["q_norm"]["g"], eps), inv, amp)
    k = _rope(_rmsnorm(k, p["k_norm"]["g"], eps), inv, amp)
    q = q.reshape(t, hkv, h // hkv, dh)   # query head j reads kv head j // rep
    outs = []
    for s in range(0, t, q_block):
        e = min(t, s + q_block)
        lo = 0 if window is None else max(0, s - (window - 1))
        scores = jnp.einsum("qgrd,kgd->grqk", q[s:e], k[lo:e]) / math.sqrt(dh)
        at, keys = jnp.arange(s, e)[:, None], jnp.arange(lo, e)[None, :]
        ok = keys <= at
        if window is not None:
            ok &= keys > at - window
        prob = jax.nn.softmax(jnp.where(ok[None, None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("grqk,kgd->qgrd", prob, v[lo:e]))
    o = jnp.concatenate(outs, axis=0).reshape(t, h * dh)
    return o @ p["wo"].astype(F32)


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


def expert_layer(x, moe, cfg, chosen=None):
    """The routed feed-forward on the normed x [T, D]: one expert after
    another on every token; a token keeps the result of an expert it chose,
    times that expert's weight -> (y [T, D], route(...))."""
    r = route(x, moe, cfg, chosen)
    w, e = r[0], r[1]

    def one(i, out):
        wi = jnp.sum(jnp.where(e == i, w, 0.0), -1, keepdims=True)
        up = jax.nn.silu(x @ moe["w_gate"][i].astype(F32)) * (
            x @ moe["w_up"][i].astype(F32))
        return out + wi * (up @ moe["w_down"][i].astype(F32))

    return jax.lax.fori_loop(0, moe["w_gate"].shape[0], one,
                             jnp.zeros_like(x)), r


# ---- the model --------------------------------------------------------------
def _forward(params, tokens, cfg, chosen=None):
    """tokens [T] -> (final-normed hidden states [T, D], per layer the
    routing of ``route``). ``chosen`` [L, T, k] makes every layer use those
    experts."""
    eps = cfg["rms_norm_eps"]
    h = params["embed"].astype(F32)[tokens]
    routes = []
    for blk, kind in zip(params["blocks"], cfg["layer_types"]):
        h = h + attn_mixer(_rmsnorm(h, blk["ln1"]["g"], eps), blk["attn"],
                           kind, cfg)
        y, r = expert_layer(_rmsnorm(h, blk["ln2"]["g"], eps), blk["moe"],
                            cfg, None if chosen is None
                            else chosen[len(routes)])
        routes.append(r)
        h = h + y
    return _rmsnorm(h, params["ln_f"]["g"], eps), routes


def _logits(params, hidden):
    return hidden @ params["head"].astype(F32).T


def _key(cfg):
    return json.dumps({**{k: cfg[k] for k in KEYS},
                       "control": cfg.get("control")}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jit_tail(cfg_key, n_tail):
    cfg = json.loads(cfg_key)

    def f(params, tokens, real_len, chosen):
        hid, routes = _forward(params, tokens, cfg, chosen)
        start = jnp.maximum(real_len - n_tail, 0)
        tail = jax.lax.dynamic_slice_in_dim(hid, start, n_tail)
        return _logits(params, tail), routes

    return jax.jit(f)


def forward_tail(params, tokens, cfg, n_tail, pad_to=None, chosen=None):
    """One forward over ``tokens`` ([T] ints) -> ``(logits, routes)``:
    teacher-forced float32 logits at the last ``min(n_tail, T)`` positions
    against the whole context, and each layer's routing of every position as
    ``(weights [T, k], experts [T, k], lead [T], shortfall [T])``
    (``route``). With ``chosen`` ([L, T, k] ints: the experts another
    implementation chose) the reference computes the model with *those*
    experts, weighted by its own probabilities of them, and ``shortfall``
    says whether the choice was admissible.

    ``pad_to`` pads the sequence on the right so that few lengths compile:
    causality and the per-token experts make the pad inert for the positions
    before it."""
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


def loss(params, tokens, cfg):
    """Mean next-token cross entropy of ``tokens`` [T] (what
    ``TransformerLM.loss`` computes for a batch of one); differentiable in
    ``params``."""
    with jax.default_matmul_precision("highest"):
        hid, _ = _forward(params, jnp.asarray(tokens, jnp.int32), cfg)
        logp = jax.nn.log_softmax(_logits(params, hid[:-1]), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(tokens, jnp.int32)[1:, None], axis=-1))
