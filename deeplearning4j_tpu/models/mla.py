"""Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434 section
2.1): the full-attention mixer of a hybrid block, as ``TransformerLM._block``
runs it.

    [q_nope (H x dn) ; q_rope (H x dr)] = x Wq                 or, with query
    c_q = rmsnorm(x Wqa) ;  [q_nope ; q_rope] = c_q Wqb        compression
    [c~ (r) ; k_r~ (dr)]               = x Wdkv ;  c = rmsnorm(c~)
    [k_nope (H x dn) ; v (H x dv)]     = c Wukv
    q_rope, k_r = rope(.)              k_r is one key shared by all heads
    p_h(t, s)   = softmax_{s<=t}((q_nope_h . k_nope_h + q_rope_h . k_r)
                                 / sqrt(dn + dr))
    y           = concat_h((sum_s p_h v_h) * sigmoid(x Wg)_h) Wo

The model's description says which (``TransformerLM(mla=)``): a
``q_lora_rank`` gives the compressed query (``wq_a``, ``q_norm``, ``wq_b`` in
place of ``wq``; Ling has none, GLM-5.2 2,048), ``gate: False`` leaves the
head-wise output gate ``wg`` out (Ling has one, GLM-5.2 none). The functions
read it off the parameters they are given. ``dv`` need not equal ``dn``.

What a position leaves behind is its latent row ``[c ; k_r]`` after the
norm and after RoPE: ``r + dr`` numbers whatever the number of heads.
``qk_rope_head_dim`` 0 (GLM-5.3's ``mla_use_nope``) is latent attention
without a rotary part: ``q_rope`` and ``k_r`` are empty, the row is ``c``
alone and the scale ``dn^-1/2``.

Two forms: ``attend_full`` expands every position's keys and values from
its latent (prefill, training: causal attention over 32 heads, the flash
kernel from 4,096 positions); ``attend_latent`` (decode) absorbs ``Wuk``
into the query and ``Wuv`` into the output, so that the scores are taken
against the cached latent rows themselves, one ``r + dr`` wide key shared
by all query heads. Scopes ``mla.proj`` and ``mla.attend``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.scopes import scope

__all__ = ["QUERY_BLOCK", "init_mla", "compress_query", "mla_project",
           "attend_full", "attend_latent", "mla_output", "softmax_scale",
           "by_query_blocks"]

# queries whose scores and attention logits are alive at once: at GLM-5.2's
# sizes against 28,672 keys, [128, 32, T] float32 index scores are 470 MB
# and [64, 128, T] float32 logits 940 MB (1.45 GiB of temporaries a block
# program, compile-only, PERF.md section 4)
QUERY_BLOCK = 128


def softmax_scale(dims: Dict[str, Any]) -> float:
    """``(dn + dr)^-1/2``, times ``dims["softmax_mult"]`` where the model
    scales its rotary frequencies (YaRN's ``mscale_all_dim``, squared:
    ``TransformerLM(rope_scaling=)``)."""
    return ((dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]) ** -0.5
            * float(dims.get("softmax_mult", 1.0)))


def by_query_blocks(fn, *args, block: Optional[int] = None):
    """``fn`` on ``args`` ([b, q, ...] each) ``block`` (default
    ``QUERY_BLOCK``, read at the call) queries at a time, where q is a larger
    multiple of it; the results joined on q."""
    block = block or QUERY_BLOCK
    b, q = args[0].shape[:2]
    if q <= block or q % block:
        return fn(*args)
    n = q // block

    def split(a):       # [b, q, ...] -> [n, b, block, ...]
        return jnp.moveaxis(a.reshape((b, n, block) + a.shape[2:]), 1, 0)

    def join(a):
        a = jnp.moveaxis(a, 0, 1)
        return a.reshape((b, q) + a.shape[3:])

    out = lax.map(lambda xs: fn(*xs), tuple(split(a) for a in args))
    return jax.tree_util.tree_map(join, out)


def init_mla(key, d_model: int, num_heads: int, dims: Dict[str, int],
             dtype) -> Dict[str, Any]:
    """Glorot-normal ``wq`` [D, H (dn + dr)], ``wdkv`` [D, r + dr], ``wukv``
    [r, H (dn + dv)], ``wo`` [H dv, D], ``wg`` [D, H]; ``kv_norm.g`` [r].
    With ``dims["q_lora_rank"]`` = rq: ``wq_a`` [D, rq], ``q_norm.g`` [rq],
    ``wq_b`` [rq, H (dn + dr)] instead of ``wq``; with ``dims["gate"]``
    false no ``wg``."""
    ks = jax.random.split(key, 6)
    r, dn, dr, dv = (dims[n] for n in ("kv_lora_rank", "qk_nope_head_dim",
                                       "qk_rope_head_dim", "v_head_dim"))

    def glorot(k, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dtype)
        return jax.random.normal(k, (fan_in, fan_out), dtype) * scale

    h = num_heads
    p = {"wdkv": glorot(ks[1], d_model, r + dr),
         "kv_norm": {"g": jnp.ones((r,), dtype)},
         "wukv": glorot(ks[2], r, h * (dn + dv)),
         "wo": glorot(ks[3], h * dv, d_model)}
    rq = dims.get("q_lora_rank")
    if rq:
        p.update(wq_a=glorot(ks[0], d_model, rq),
                 q_norm={"g": jnp.ones((rq,), dtype)},
                 wq_b=glorot(ks[5], rq, h * (dn + dr)))
    else:
        p["wq"] = glorot(ks[0], d_model, h * (dn + dr))
    if dims.get("gate", True):
        p["wg"] = glorot(ks[4], d_model, h)
    return p


def compress_query(x, p, *, rmsnorm, cast: Callable = lambda w: w):
    """``c_q = rmsnorm(x Wqa)`` [b, t, rq], the compressed query that the
    query heads (and a lightning indexer's, ``models/dsa.py``) are made
    from; ``None`` for parameters without query compression."""
    if "wq_a" not in p:
        return None
    with scope("mla.proj"):
        return rmsnorm(x @ cast(p["wq_a"]), p["q_norm"]["g"])


def mla_project(x, p, *, num_heads: int, dims: Dict[str, int], rope,
                rmsnorm, cast: Callable = lambda w: w, c_q=None):
    """``x`` [b, t, D] -> ``(q_nope [b, t, H, dn], q_rope [b, t, H, dr],
    latent [b, t, r + dr], gate [b, t, H] or None)``: queries after RoPE
    (from ``c_q`` = ``compress_query``'s where the parameters compress the
    query), the position's latent row ``[c ; k_r]`` after norm and RoPE,
    the head-wise output gate where there is one. ``rope(a [b, t, h, dr])``
    and ``rmsnorm(a, g)`` are the model's."""
    b, t, _ = x.shape
    r, dn, dr = (dims[n] for n in ("kv_lora_rank", "qk_nope_head_dim",
                                   "qk_rope_head_dim"))
    with scope("mla.proj"):
        q = (x @ cast(p["wq"]) if c_q is None else c_q @ cast(p["wq_b"]))
        q = q.reshape(b, t, num_heads, dn + dr)
        # no rotary part (NoPE): q_rope and k_r are empty, nothing to turn
        turn = rope if dr else (lambda a: a)
        q_nope, q_rope = q[..., :dn], turn(q[..., dn:])
        down = x @ cast(p["wdkv"])
        c = rmsnorm(down[..., :r], p["kv_norm"]["g"])
        k_r = turn(down[..., r:][:, :, None, :])[:, :, 0]
        gate = (jax.nn.sigmoid((x @ cast(p["wg"])).astype(jnp.float32))
                if "wg" in p else None)
    return q_nope, q_rope, jnp.concatenate([c, k_r], axis=-1), gate


def attend_full(q_nope, q_rope, latent, p, *, dims, attention,
                cast: Callable = lambda w: w):
    """Causal attention with every position's keys and values expanded
    from its latent. ``attention(q, k, v, scale)`` is the model's causal
    core on ``q, k`` [b, t, H, dn + dr] and ``v`` [b, t, H, dv]. Returns
    ``o`` [b, t, H, dv]."""
    b, t, h, dn = q_nope.shape
    r, dv = dims["kv_lora_rank"], dims["v_head_dim"]
    with scope("mla.proj"):
        up = (latent[..., :r] @ cast(p["wukv"])).reshape(b, t, h, dn + dv)
        k_r = jnp.broadcast_to(latent[:, :, None, r:],
                               (b, t, h, latent.shape[-1] - r))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([up[..., :dn], k_r.astype(up.dtype)], axis=-1)
    with scope("mla.attend"):
        return attention(q, k, up[..., dn:], softmax_scale(dims))


def attend_latent(q_nope, q_rope, rows, mask, p, *, dims,
                  cast: Callable = lambda w: w):
    """The absorbed form over cached latent rows: ``q_nope`` [b, q, H, dn],
    ``q_rope`` [b, q, H, dr], ``rows`` [b, T, >= r + dr] (each row's own
    history; lanes past ``r + dr`` are zeros and meet zeros of the query),
    ``mask`` [b, q, T] (keys a query may see). Returns ``o`` [b, q, H, dv],
    equal to ``attend_full`` over the same positions. The values are read
    as whole rows and the result cut to ``r``: a slice of the cache's
    minor axis would be a copy of the cache."""
    r, dn = dims["kv_lora_rank"], q_nope.shape[-1]
    h = q_nope.shape[2]
    with scope("mla.attend"):
        w = cast(p["wukv"]).reshape(r, h, -1)
        wuk, wuv = w[..., :dn], w[..., dn:]
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, wuk)
        q_cat = jnp.concatenate(
            [q_lat.astype(rows.dtype), q_rope.astype(rows.dtype)], axis=-1)
        q_cat = jnp.pad(q_cat, ((0, 0),) * 3 + (
            (0, rows.shape[-1] - q_cat.shape[-1]),))
        logits = jnp.einsum("bqhc,btc->bhqt", q_cat, rows,
                            preferred_element_type=jnp.float32
                            ) * softmax_scale(dims)
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhqt,btr->bqhr", probs.astype(rows.dtype),
                           rows)[..., :r]
        return jnp.einsum("bqhr,rhd->bqhd", o_lat.astype(q_nope.dtype), wuv)


def mla_output(o, gate, p, cast: Callable = lambda w: w):
    """``o`` [b, t, H, dv] gated a head (``gate`` None: no gate) and
    projected back to ``D``."""
    b, t = o.shape[:2]
    with scope("mla.proj"):
        if gate is not None:
            o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
        return o.reshape(b, t, -1) @ cast(p["wo"])
