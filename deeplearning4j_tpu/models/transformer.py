"""Decoder-only transformer LM: the long-context / distributed flagship.

Greenfield beyond the reference's layer zoo (pre-transformer codebase), built
to exercise the framework's modern parallelisms end-to-end:
- data parallel: batch sharded over ``data``
- tensor parallel: attention heads + MLP hidden sharded over ``model``
  (Megatron split: wq/wk/wv column, wo row; w1 column, w2 row)
- sequence/context parallel: ring attention over ``sequence``
  (parallel/ring_attention.py)

Pure-functional: params are a pytree; ``train_step`` is one jitted XLA
program (pre-norm blocks, Adam, causal LM loss). bf16 compute / f32 params
via the dtype policy.
"""

from __future__ import annotations

import functools
import logging
import math
import os
from typing import Any, Dict, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu import dtypes as dtypes_mod
from deeplearning4j_tpu.analysis.annotations import traced
from deeplearning4j_tpu.compile_cache import ensure_compile_cache
from deeplearning4j_tpu.models import dsa as dsa_mod
from deeplearning4j_tpu.models import gdn as gdn_mod
from deeplearning4j_tpu.models import hc as hc_mod
from deeplearning4j_tpu.models import kda as kda_mod
from deeplearning4j_tpu.models import mla as mla_mod
from deeplearning4j_tpu.models import ret as ret_mod
from deeplearning4j_tpu.models import routed_experts
from deeplearning4j_tpu.monitor import tracer
from deeplearning4j_tpu.ops.attention import (
    dot_product_attention,
    grouped_query_attention,
)
from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    SEQUENCE_AXIS,
)
from deeplearning4j_tpu.parallel.ring_attention import ring_attention
from deeplearning4j_tpu.pallas.flash_attention import (
    flash_attention, flash_default_interpret)
from deeplearning4j_tpu.scopes import scope

logger = logging.getLogger(__name__)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 mscale ln(factor) + 1`` (1 for a
    factor of at most 1 or an ``mscale`` of 0)."""
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(d: int, base: float, scaling: Optional[Dict[str, Any]]):
    """The ``d / 2`` rotary frequencies ``f_i = base^(-2i/d)`` as a numpy
    float64 array, scaled by YaRN (arXiv:2309.00071, "NTK-by-parts") where
    ``scaling`` = ``{factor, original_max_position_embeddings, beta_fast,
    beta_slow}`` says so: a dimension that turns more than ``beta_fast``
    times within the original context keeps its frequency, one that turns
    less than ``beta_slow`` times has it divided by ``factor``, and a linear
    ramp over the dimension's index joins the two:

        corr(n) = d ln(L / (2 pi n)) / (2 ln base)
        low, high = floor(corr(beta_fast)), ceil(corr(beta_slow))  in [0, d-1]
        ramp_i = clip((i - low) / (high - low), 0, 1)
        f'_i   = f_i (1 - ramp_i) + (f_i / factor) ramp_i"""
    half = d // 2
    freqs = np.power(base, -np.arange(half, dtype=np.float64) / half)
    if not scaling:
        return freqs
    length = scaling["original_max_position_embeddings"]

    def corr(turns):
        return d * math.log(length / (2 * math.pi * turns)) / (
            2 * math.log(base))

    low = max(math.floor(corr(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(scaling.get("beta_slow", 1))), d - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return freqs * (1 - ramp) + freqs / scaling["factor"] * ramp


def _rope(x, positions, base: float = 10000.0, interleaved: bool = False,
          scaling: Optional[Dict[str, Any]] = None):
    """Rotary position embedding on [b, t, h, d] at absolute ``positions``
    (may be traced): [t] shared across the batch (training/prefill), or
    [b, t] per-row (the serving decode step, where every slot sits at its
    own position). Angles in f32, result in x's dtype. Rotation is
    applied to q/k BEFORE attention, so it composes unchanged with the
    XLA, Pallas-flash, and ring paths. ``base`` and the pairing are the
    model's (``rope_theta``, ``rope_interleaved``): a pair is dimensions
    ``(i, i + d/2)``, or ``(2i, 2i + 1)`` when interleaved. ``scaling``
    (``rope_scaling``): YaRN's frequencies (``rope_frequencies``), and cos
    and sin times ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)``, which is 1 where the two are equal."""
    if interleaved:     # bring each pair to (i, i + d/2), rotate, put back
        shape = x.shape
        halves = jnp.swapaxes(x.reshape(shape[:-1] + (-1, 2)), -1, -2)
        out = _rope(halves.reshape(shape), positions, base, scaling=scaling)
        return jnp.swapaxes(out.reshape(shape[:-1] + (2, -1)), -1, -2
                            ).reshape(shape)
    d = x.shape[-1]
    half = d // 2
    if scaling:
        freqs = jnp.asarray(rope_frequencies(d, base, scaling), jnp.float32)
    else:
        freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    if positions.ndim == 1:       # [t, half] -> broadcast over batch
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling:
        amp = yarn_mscale(scaling["factor"], scaling.get("mscale", 1)) / (
            yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0)))
        if amp != 1.0:
            cos, sin = cos * amp, sin * amp
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _layernorm(x, g, b, eps=1e-5):
    # statistics in >=f32, but the result stays in x's dtype: multiplying
    # by the f32 g/b params directly would promote the whole residual
    # stream to f32 and silently turn every downstream matmul into an
    # f32 MXU op (measured 11.9% -> 14.0% MFU on the t=1024 bench config;
    # the rest of the gap is the materialized [b,h,t,t] score matrix)
    st = jnp.promote_types(x.dtype, jnp.float32)
    xs = x.astype(st)
    mean = jnp.mean(xs, axis=-1, keepdims=True)
    var = jnp.var(xs, axis=-1, keepdims=True)
    y = (xs - mean) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(st) + b.astype(st)).astype(x.dtype)


def _rmsnorm(x, g, eps: float = 1e-5):
    # weight only; statistics in >=f32, result in x's dtype (as _layernorm)
    st = jnp.promote_types(x.dtype, jnp.float32)
    xs = x.astype(st)
    y = xs * jax.lax.rsqrt(jnp.mean(xs * xs, axis=-1, keepdims=True) + eps)
    return (y * g.astype(st)).astype(x.dtype)


class TransformerLM:
    def __init__(self, vocab_size: int, d_model: int = 256, num_heads: int = 8,
                 num_layers: int = 4, d_ff: Optional[int] = None,
                 max_len: int = 512, lr: float = 3e-4, seed: int = 0,
                 dtype_policy: str = "float32", attn_impl: str = "auto",
                 remat: bool = False, pos_encoding: str = "learned",
                 num_kv_heads: Optional[int] = None,
                 attn_window: Optional[int] = None,
                 sp_impl: str = "ring", scan_layers: bool = False,
                 norm: str = "layernorm", qk_norm: bool = False,
                 num_experts: int = 0, experts_per_token: int = 0,
                 norm_topk_prob: bool = False,
                 tie_embeddings: bool = True,
                 rope_theta: float = 10000.0, rope_interleaved: bool = False,
                 norm_eps: float = 1e-5,
                 mixers: Optional[Sequence[str]] = None,
                 ffns: Optional[Sequence[str]] = None,
                 glu_width: Optional[int] = None,
                 kda: Optional[Dict[str, Any]] = None,
                 mla: Optional[Dict[str, Any]] = None,
                 moe: Optional[Dict[str, Any]] = None,
                 indexers: Optional[Sequence[Optional[str]]] = None,
                 dsa: Optional[Dict[str, Any]] = None,
                 rope_scaling: Optional[Dict[str, Any]] = None,
                 mtp: Optional[Dict[str, Any]] = None,
                 gdn: Optional[Dict[str, Any]] = None,
                 attn: Optional[Dict[str, Any]] = None,
                 ret: Optional[Dict[str, Any]] = None,
                 hc: Optional[Dict[str, Any]] = None):
        assert d_model % num_heads == 0
        # The block, described per model; the defaults are StarCoder2's
        # (LayerNorm with bias, biased GELU MLP, tied unembedding).
        # norm: "layernorm" (gain and bias) | "rmsnorm" (gain only), for
        # the block's two norms and the final one. qk_norm: an RMSNorm
        # over the whole q and k projections, before the split into heads
        # and before RoPE. num_experts > 0: the feed-forward is
        # ``experts_per_token`` of ``num_experts`` routed SwiGLU experts of
        # width ``d_ff`` without bias (models/routed_experts.py: dropless,
        # float32 router; ``norm_topk_prob`` renormalises the chosen
        # weights) instead of the dense MLP. tie_embeddings=False: the
        # unembedding is its own ``head`` leaf, shaped like ``embed``.
        if norm not in ("layernorm", "rmsnorm"):
            raise ValueError(
                f"norm={norm!r} must be 'layernorm' or 'rmsnorm'")
        if num_experts < 0 or (num_experts and not
                               1 <= experts_per_token <= num_experts):
            raise ValueError(
                f"experts_per_token={experts_per_token} must be in "
                f"[1, num_experts={num_experts}]")
        # The block, described per layer (defaults: every layer the block
        # above). mixers[i]: "attn" (GQA/MHA with RoPE or learned positions,
        # above) | "kda" (Kimi Delta Attention, models/kda.py; ``kda`` =
        # {head_dim, conv, lower}: a recurrent [H, dk, dk] state and a
        # convolution tail instead of keys and values) | "mla" (latent
        # attention, models/mla.py; ``mla`` = {kv_lora_rank,
        # qk_nope_head_dim, qk_rope_head_dim, v_head_dim}: one latent row a
        # position). ffns[i]: "mlp" (the biased GELU MLP) | "glu" (a dense
        # SwiGLU of width ``glu_width``, no bias) | "moe" (routed experts of
        # width ``d_ff``). ``moe`` describes a router and a share other
        # than OLMoE's (models/routed_experts.py): {n_group, topk_group,
        # scale, bias, shared_width, first, held}: group-limited sigmoid
        # routing over ``num_experts``, of which ``held`` starting at
        # ``first`` live here, and a shared expert. ``rope_theta`` /
        # ``rope_interleaved`` / ``norm_eps``: RoPE's base and pairing, the
        # norms' epsilon. ``mla`` may also give ``q_lora_rank`` (the query
        # is compressed: ``wq_a``, a norm, ``wq_b``) and ``gate: False`` (no
        # head-wise output gate). indexers[i], for an "mla" layer of a model
        # with learned sparse attention (models/dsa.py; ``dsa`` = {n_heads,
        # head_dim, topk, rope_dim}): "full" (the layer has a lightning
        # indexer, selects ``topk`` positions a query and attends them) |
        # "shared" (it attends the selection of the nearest "full" layer
        # before it) | None (it attends every position).
        # ``rope_scaling`` = {factor, original_max_position_embeddings,
        # beta_fast, beta_slow, mscale, mscale_all_dim}: YaRN on every RoPE
        # of the model (``rope_frequencies``); with ``mscale_all_dim`` an
        # 'mla' layer's softmax scale is multiplied by
        # ``yarn_mscale(factor, mscale_all_dim)`` squared, as the
        # ``deepseek_v3`` modelling code does. ``mtp`` = {loss_weight}: the
        # model has a multi-token-prediction module of depth 1 (DeepSeek-V3,
        # arXiv:2412.19437 section 2.2; ``mtp_logits``): one more block, of
        # the last layer's kind, that reads the model's final-normed hidden
        # state at position i beside the embedding of token i + 1 and
        # predicts token i + 2 through the model's own embedding and head.
        # ``loss`` adds its cross entropy times ``loss_weight``, and
        # ``serving.DecodeServer`` drafts from it (speculative rounds).
        # mixers[i] may also be "gdn" (Gated DeltaNet, models/gdn.py; ``gdn``
        # = {key_heads, value_heads, head_dim, conv}: a recurrent [Hv, dk,
        # dk] state and a convolution tail, one decay a head). ``attn``
        # describes an "attn" layer whose sizes are its own: {head_dim (not
        # d_model // num_heads), rotary_dim (RoPE on the first so many
        # dimensions of a head; default all), head_norm (an RMSNorm over
        # each head of q and of k, gain [head_dim], instead of ``qk_norm``'s
        # over the whole projection), gate (``wq`` is twice as wide: per
        # head, head_dim of query then head_dim of gate, and the heads'
        # output is multiplied by sigmoid(gate) before ``wo``), windows (a
        # sliding window a layer, None on a layer that attends its whole
        # prefix and on every layer that is no 'attn' layer, instead of the
        # model's one ``attn_window``; a served slot keeps a ring of rows
        # for the window layers beside the T_max rows of the others,
        # ``serving/kv_cache.py``), rope ({"window": {theta, scaling},
        # "full": {theta, scaling}}: RoPE's base and YaRN scaling by the
        # layer's kind, whichever of the two it names, instead of the
        # model's ``rope_theta`` / ``rope_scaling``)}. ``moe``
        # without ``n_group`` keeps the softmax router over ``num_experts``
        # and names the share alone ({first, held}); ``shared_gate``: the
        # shared expert's output times sigmoid(x . w), one number a token.
        # mixers[i] may also be "ret" (power retention, models/ret.py; ``ret``
        # = {power}: ``num_heads`` queries over ``num_kv_heads`` key/value
        # heads of ``head_dim``, per-head RMSNorm and the model's RoPE on q
        # and k, a gate a key/value head; a [Hkv, D, dh] float32 state and
        # its normaliser [Hkv, dh, dh] instead of rows a position; degree 2
        # is the one written). ``kda`` may also give ``gate_rank`` and
        # ``out_gate: "channel"`` (Kimi Linear's low-rank decay gate and
        # channel-wise output gate, ``models/kda.init_kda``). ``mla`` with
        # ``qk_rope_head_dim`` 0 is latent attention without a rotary part.
        # ``dsa`` may also give ``pool`` (index keys pooled ``pool`` positions
        # to a key: the indexer scores pools, the selection is ``topk / pool``
        # pools and the query's own open pool, ``models/dsa.py``).
        # ``swiglu_limit`` in ``moe`` clamps every expert's and dense GLU's
        # gate to at most the limit and its up projection to within it,
        # before their product. ``hc`` = {streams, sinkhorn_iters, eps}: the
        # residual is ``streams`` streams mixed by manifold-constrained
        # hyper-connections round every sub-layer (``models/hc.py``); absent,
        # the plain residual ``h + y``.
        kinds = ("attn", "kda", "mla", "gdn", "ret"), ("mlp", "glu", "moe")
        self.mixers = tuple(mixers) if mixers is not None else (
            "attn",) * num_layers
        self.ffns = tuple(ffns) if ffns is not None else (
            "moe" if num_experts else "mlp",) * num_layers
        for name, got, ok in (("mixers", self.mixers, kinds[0]),
                              ("ffns", self.ffns, kinds[1])):
            if len(got) != num_layers or set(got) - set(ok):
                raise ValueError(f"{name}={got!r} must name one of {ok} "
                                 f"for each of the {num_layers} layers")
        for kind, sizes, used in (("kda", kda, self.mixers),
                                  ("mla", mla, self.mixers),
                                  ("gdn", gdn, self.mixers),
                                  ("ret", ret, self.mixers),
                                  ("glu", glu_width, self.ffns),
                                  ("moe", num_experts, self.ffns)):
            if kind in used and not sizes:
                raise ValueError(f"a {kind!r} layer needs its sizes (kda=, "
                                 "mla=, gdn=, ret=, glu_width=, "
                                 "num_experts=)")
        self.indexers = tuple(indexers) if indexers is not None else (
            None,) * num_layers
        if len(self.indexers) != num_layers or dsa is None and any(
                self.indexers):
            raise ValueError(f"indexers={indexers!r} must name None, 'full' "
                             f"or 'shared' for each of the {num_layers} "
                             "layers, with the indexer's sizes in dsa=")
        for i, kind in enumerate(self.indexers):
            if kind not in (None, "full", "shared") or kind and (
                    self.mixers[i] != "mla" or not (mla or {}).get(
                        "q_lora_rank")):
                raise ValueError(
                    f"indexers[{i}]={kind!r}: an indexer is 'full' or "
                    "'shared' and belongs to an 'mla' layer with a "
                    "compressed query (mla['q_lora_rank'])")
            if kind == "shared" and "full" not in self.indexers[:i]:
                raise ValueError(f"indexers[{i}]='shared' has no 'full' "
                                 "layer before it to take a selection from")
        self.dsa = dict(dsa) if dsa else None
        self.kda = dict(kda) if kda else None
        self.mla = dict(mla) if mla else None
        self.gdn = dict(gdn) if gdn else None
        if self.gdn and self.gdn["value_heads"] % self.gdn["key_heads"]:
            raise ValueError(
                f"gdn: value_heads={self.gdn['value_heads']} must be a "
                f"multiple of key_heads={self.gdn['key_heads']}")
        self.ret = dict(ret) if ret else None
        self.hc = dict(hc) if hc else None
        if self.hc and (mtp or int(self.hc["streams"]) < 1):
            raise ValueError(
                f"hc={hc!r} with mtp={mtp!r}: hyper-connections need "
                "streams >= 1, and the multi-token-prediction module reads "
                "one residual stream")
        if self.ret and (self.ret.get("power", 2) != 2
                         or pos_encoding != "rope"):
            raise ValueError(
                f"ret={ret!r} with pos_encoding={pos_encoding!r}: power "
                "retention is written for degree 2 (power=2: the state is "
                "the symmetric square of a key) and takes the model's RoPE")
        self.attn = dict(attn) if attn else None
        self.head_dim = int((attn or {}).get("head_dim",
                                             d_model // num_heads))
        self.rotary_dim = int((attn or {}).get("rotary_dim", self.head_dim))
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        by_kind = dict((attn or {}).get("rope") or {})
        if set(by_kind) - {"window", "full"}:
            raise ValueError(f"attn['rope'] names the layer kinds 'window' "
                             f"and 'full' (got {sorted(by_kind)})")
        for scaling in [rope_scaling] + [
                r.get("scaling") for r in by_kind.values()]:
            kind = (scaling or {}).get("rope_type", "yarn")
            if kind != "yarn" or scaling and pos_encoding != "rope":
                raise ValueError(f"rope_scaling: rope_type {kind!r} with "
                                 f"pos_encoding={pos_encoding!r}; YaRN over "
                                 "RoPE is the one scaling written")
        # {layer kind: (base, scaling)} where ``attn['rope']`` names the kind
        self.rope_by_kind = {
            kind: (float(r.get("theta", rope_theta)),
                   dict(r["scaling"]) if r.get("scaling") else None)
            for kind, r in by_kind.items()}
        if self.rope_scaling and self.mla:
            # read by ``models/mla.softmax_scale``; set, not multiplied, so a
            # model rebuilt from ``get_config`` carries it once
            self.mla["softmax_mult"] = yarn_mscale(
                self.rope_scaling["factor"],
                self.rope_scaling.get("mscale_all_dim", 0)) ** 2
        self.mtp = dict(mtp) if mtp else None
        if self.dsa and self.dsa.get("pool", 1) > 1 and (
                self.dsa["topk"] % self.dsa["pool"]
                or "shared" in self.indexers):
            raise ValueError(
                f"dsa={dsa!r}: pooled index keys select topk / pool whole "
                "pools, each layer for itself (no 'shared' indexer)")
        if self.mtp and (pos_encoding != "rope" or self.mixers[-1] != "mla"
                         or self.indexers[-1]):
            raise ValueError(
                "mtp= is written for a model with RoPE whose last layer is "
                "'mla' without an indexer: the module's block is one more "
                "layer of that kind")
        self.moe = dict(moe) if moe else None
        # the clamp of every expert's and dense GLU's gate and up projection
        # (``routed_experts.swiglu``); None: no clamp
        self.swiglu_limit = float((moe or {}).get("swiglu_limit") or 0) or None
        self.glu_width = glu_width
        self.rope_theta = float(rope_theta)
        self.rope_interleaved = bool(rope_interleaved)
        self.norm_eps = float(norm_eps)
        self.norm = norm
        self.qk_norm = bool(qk_norm)
        self.num_experts = int(num_experts)
        self.experts_per_token = int(experts_per_token) if num_experts else 0
        self.norm_topk_prob = bool(norm_topk_prob)
        self.tie_embeddings = bool(tie_embeddings)
        # "auto": Pallas flash kernel when a TPU backend is attached and
        # head_dim maps onto lane tiles; "xla" / "flash" force a path
        assert attn_impl in ("auto", "xla", "flash")
        self.attn_impl = attn_impl
        # "learned": additive position table (the default, bounded by
        # max_len); "rope": rotary embedding on q/k — relative positions,
        # the modern long-context choice
        assert pos_encoding in ("learned", "rope")
        if pos_encoding == "rope" and (
                self.rotary_dim % 2 or self.rotary_dim > self.head_dim):
            raise ValueError(
                f"RoPE needs an even rotary_dim within head_dim (got "
                f"rotary_dim {self.rotary_dim} of head_dim {self.head_dim}; "
                f"both are d_model={d_model} / num_heads={num_heads} unless "
                "attn= says otherwise): the rotation pairs dimensions")
        self.pos_encoding = pos_encoding
        # GQA/MQA: fewer key/value heads than query heads — KV cache and
        # wk/wv params shrink by num_heads/num_kv_heads; K/V are repeated
        # across each query-head group at attention time
        self.num_kv_heads = num_heads if num_kv_heads is None else num_kv_heads
        if self.num_kv_heads < 1 or num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_kv_heads={self.num_kv_heads} must be >= 1 and divide "
                f"num_heads={num_heads}")
        # sliding-window local attention: each query sees only the last
        # attn_window keys (None = full causal attention); composes with
        # the XLA, grouped, flash, ring, and ulysses paths
        if attn_window is not None and attn_window < 1:
            raise ValueError(f"attn_window={attn_window} must be >= 1")
        self.attn_window = attn_window
        # the window a layer: ``attn['windows']``, else the model's one
        windows = (attn or {}).get("windows")
        self.windows = (attn_window,) * num_layers if windows is None else (
            tuple(windows))
        if windows is not None and (
                attn_window is not None or len(self.windows) != num_layers
                or any(w is not None and (w < 1 or m != "attn")
                       for w, m in zip(self.windows, self.mixers))):
            raise ValueError(
                f"attn['windows']={windows!r} gives a window >= 1 or None for "
                f"each of the {num_layers} layers (None on a layer that is no "
                "'attn' layer), in place of attn_window")
        if self.mtp and self.by_layer:
            raise ValueError(
                "mtp= with attn['windows'] or attn['rope']: the module's "
                "block is one more layer, which has no place in a "
                "description by layer")
        # sequence-parallel strategy when training with
        # sequence_parallel=True: "ring" (K/V rotate around the sequence
        # axis via ppermute — best at huge T) or "ulysses" (two
        # all-to-alls reshard sequence<->heads — best when heads >= ring
        # size and ICI all-to-all bandwidth is plentiful). Switchable per
        # model; parallel/ulysses.py documents the trade.
        if sp_impl not in ("ring", "ulysses"):
            raise ValueError(f"sp_impl={sp_impl!r} must be 'ring' or "
                             "'ulysses'")
        self.sp_impl = sp_impl
        # scan_layers: run the block stack as ONE lax.scan over stacked
        # per-layer params instead of a Python loop — the traced program
        # holds ONE block body regardless of depth (asserted on the scan
        # jaxpr in tests), so the block math XLA must optimize stops
        # scaling with num_layers; per-layer cost drops to a dozen
        # trivial stacking ops (the deep serve/bench configs'
        # compile-time bound). Composes with remat: the checkpoint wraps
        # the scan BODY, preserving the O(sqrt) activation-memory trade.
        self.scan_layers = bool(scan_layers)
        # remat: recompute each block's activations in the backward pass
        # (jax.checkpoint) instead of keeping them live across the whole
        # step — trades ~1/3 more FLOPs for O(sqrt) activation memory, the
        # standard TPU HBM lever for large batch x seq products
        self.remat = remat
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.num_heads = num_heads
        self.num_layers = num_layers
        self.d_ff = d_ff or 4 * d_model
        self.max_len = max_len
        self.lr = lr
        self.seed = seed
        self.dtype_policy_name = dtype_policy
        self.policy = dtypes_mod.policy_from_name(dtype_policy)
        self.params: Optional[Dict[str, Any]] = None
        self.opt_state: Optional[Dict[str, Any]] = None
        self.step_count = 0

    # ------------------------------------------------------------------
    def init(self) -> "TransformerLM":
        key = jax.random.PRNGKey(self.seed)
        D, F, V, L = self.d_model, self.d_ff, self.vocab_size, self.max_len
        Dh = self.head_dim
        dt = self.policy.param_dtype

        def dense(key, fan_in, fan_out):
            return jax.random.normal(key, (fan_in, fan_out), dt) * jnp.sqrt(
                2.0 / (fan_in + fan_out)).astype(dt)

        def norm(width=D):
            p = {"g": jnp.ones((width,), dt)}
            if self.norm == "layernorm":
                p["b"] = jnp.zeros((width,), dt)
            return p

        keys = jax.random.split(key, 2 + 6 * self.num_layers)
        params: Dict[str, Any] = {
            "embed": jax.random.normal(keys[0], (V, D), dt) * 0.02,
            "ln_f": norm(),
            "blocks": [],
        }
        if self.pos_encoding == "learned":
            params["pos"] = jax.random.normal(keys[1], (L, D), dt) * 0.02
        if not self.tie_embeddings:
            params["head"] = jax.random.normal(
                jax.random.fold_in(keys[0], 1), (V, D), dt) * 0.02

        def block(k, i):
            """Layer ``i``'s block from the six keys ``k``."""
            blk = {"ln1": norm(), "ln2": norm()}
            if self.hc:     # one set of maps a sub-layer: mixer, feed-forward
                blk["hc1"] = hc_mod.init_hc(
                    jax.random.fold_in(k[0], 0x4c1), D, self.hc, dt)
                blk["hc2"] = hc_mod.init_hc(
                    jax.random.fold_in(k[4], 0x4c2), D, self.hc, dt)
            if self.mixers[i] == "kda":
                blk["kda"] = kda_mod.init_kda(
                    k[0], D, self.num_heads, self.kda["head_dim"],
                    self.kda["conv"], dt, self.kda.get("gate_rank", 0),
                    self.kda.get("out_gate", "head"))
            elif self.mixers[i] == "gdn":
                blk["gdn"] = gdn_mod.init_gdn(k[0], D, self.gdn, dt)
            elif self.mixers[i] == "ret":
                blk["ret"] = ret_mod.init_ret(
                    k[0], D, self.num_heads, self.num_kv_heads, Dh, dt)
            elif self.mixers[i] == "mla":
                blk["mla"] = mla_mod.init_mla(k[0], D, self.num_heads,
                                              self.mla, dt)
                if self.indexers[i] == "full":
                    blk["mla"]["indexer"] = dsa_mod.init_indexer(
                        k[1], D, self.mla["q_lora_rank"], self.dsa, dt)
            else:
                a = self.attn or {}
                width = self.num_heads * Dh
                blk["attn"] = {
                    "wq": dense(k[0], D, width * (2 if a.get("gate") else 1)),
                    "wk": dense(k[1], D, self.num_kv_heads * Dh),
                    "wv": dense(k[2], D, self.num_kv_heads * Dh),
                    "wo": dense(k[3], width, D),
                }
                if a.get("head_norm"):
                    blk["attn"]["q_norm"] = {"g": jnp.ones((Dh,), dt)}
                    blk["attn"]["k_norm"] = {"g": jnp.ones((Dh,), dt)}
                elif self.qk_norm:
                    blk["attn"]["q_norm"] = {"g": jnp.ones((D,), dt)}
                    blk["attn"]["k_norm"] = {
                        "g": jnp.ones((self.num_kv_heads * Dh,), dt)}
            if self.ffns[i] == "moe":
                m = self.moe or {}
                blk["moe"] = routed_experts.init_experts(
                    k[4], D, F, self.num_experts, dt, held=m.get("held"),
                    bias=bool(m.get("bias")),
                    shared_width=int(m.get("shared_width", 0)),
                    shared_gate=bool(m.get("shared_gate")))
            elif self.ffns[i] == "glu":
                G = self.glu_width
                blk["glu"] = {"w1": dense(k[4], D, G),
                              "w3": dense(jax.random.fold_in(k[4], 1), D, G),
                              "w2": dense(k[5], G, D)}
            else:
                blk["mlp"] = {
                    "w1": dense(k[4], D, F), "b1": jnp.zeros((F,), dt),
                    "w2": dense(k[5], F, D), "b2": jnp.zeros((D,), dt),
                }
            return blk

        params["blocks"] = [block(keys[2 + 6 * i:2 + 6 * (i + 1)], i)
                            for i in range(self.num_layers)]
        if self.mtp:
            # the module (``mtp_logits``): the norms of the token's embedding
            # and of the hidden state, ``proj`` [2 D, D] (``M``: rows 0..D-1
            # meet the embedding, the others the hidden state), one more
            # block of the last layer's kind, the norm before the shared head
            # (keys[1] is the learned positions' key: a model with a module
            # has RoPE and never draws from it)
            km = jax.random.split(jax.random.fold_in(keys[1], 0x3170), 7)
            params["mtp"] = {
                "enorm": norm(), "hnorm": norm(),
                "proj": dense(km[6], 2 * D, D),
                "block": block(km[:6], self.num_layers - 1),
                "norm": norm()}
        self.params = params
        self.opt_state = jax.tree_util.tree_map(
            lambda p: {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}, params)
        return self

    # ------------------------------------------------------------------
    def _head_dim_tiles(self) -> bool:
        """True when head_dim maps onto the kernel's lane tiles: the
        flash block shapes put head_dim on the minor (lane) axis, so a
        sublane-aligned head_dim >= half a lane tile keeps the MXU fed
        without pathological padding."""
        return self.head_dim >= 64 and self.head_dim % 8 == 0

    def _attn_impl(self, t: Optional[int] = None, *,
                   train: bool = False) -> str:
        """Resolve the attention path. ``DL4J_ATTN_IMPL`` (``flash`` /
        ``xla`` / ``auto``) overrides the constructor; resolution happens
        at trace time (a static choice per program — no recompile
        hazard). "auto" then means:

        - **training** (``train=True``): the Pallas flash kernel whenever
          head_dim tiles — the fwd AND bwd kernels exist
          (pallas/flash_attention.py) and keep the [t, t] score matrix in
          VMEM both directions, so training never materializes
          [b, h, t, t] f32 HBM traffic (the round-3 MFU gap's largest
          single term). Interpret-mode backends (CPU tests) stay on XLA.
        - **inference**: the measured v5e crossover — flash from t >= 4k
          (short decode/prefill shapes stay on the XLA-fused path)."""
        env = os.environ.get("DL4J_ATTN_IMPL", "").strip().lower()
        impl = self.attn_impl
        if env:
            if env not in ("auto", "xla", "flash"):
                raise ValueError(
                    f"DL4J_ATTN_IMPL={env!r} must be one of "
                    "auto/xla/flash")
            impl = env
        if impl != "auto":
            return impl
        if flash_default_interpret():
            return "xla"
        if train:
            return "flash" if self._head_dim_tiles() else "xla"
        seq = t if t is not None else self.max_len
        if seq >= 4096 and self.head_dim >= 64:
            return "flash"
        return "xla"

    @traced
    def _block(self, blk, h, *, mesh: Optional[Mesh] = None,
               sequence_parallel: bool = False, attention=None,
               positions=None, train: bool = False, live=None,
               moe_info: Optional[list] = None, state=None,
               state_kernel: bool = False,
               indexer=None, selection=None, layer: Optional[int] = None):
        """One pre-norm block on ``h`` [b, t, D], as the model describes
        that layer (``blk``'s own keys say which mixer and which
        feed-forward it is; norm kind, QK-norm — chosen here,
        at trace time, for training, prefill and decode alike; ``layer``,
        its index, says which window and which RoPE an 'attn' layer of a
        model described by layer has: ``_window``, ``_rope_head``). Returns
        ``(h, k, v)``
        with k/v in [b, t, H, Dh] — ``forward`` discards them (XLA DCE),
        the KV-cache prefill keeps them (k/v are post-RoPE under
        ``pos_encoding="rope"``). ``attention(q, k, v) -> o`` overrides
        the causal self-attention core (the KV-cache decode attends
        against the cache instead) while sharing every other line of
        block math. ``positions`` are the absolute positions for RoPE —
        [t] (default 0..t-1; the decode step passes its cache slot) or
        [b, t] per-row (the serving decode, one position per slot).

        ``live`` [b, t] (bool) marks the rows that hold a token (a
        prompt's pad tail and free slots do not): the others reach no
        routed expert and move no recurrent state. A list passed as
        ``moe_info`` receives a routed layer's routing
        (``routed_experts.routed_ffn``'s ``info``: chosen experts, their
        weights, the live load per expert held).

        The other mixers leave other things behind. A ``kda`` or ``gdn``
        layer returns ``(h, S, tail)``, its recurrent state and convolution
        tail as of each row's last live position, and continues from
        ``state`` = ``(S, tail)`` (default: a request's start); one position
        on a state runs as ``pallas/delta_step.py`` over the live rows with
        ``state_kernel`` (the serving decode step; ``kda.recur`` says where),
        else as ``kda_step``. A ``ret`` layer (power retention,
        ``models/ret.py``) returns ``(h, S, Z)``, its state and normaliser,
        continues from ``state`` = ``(S, Z)`` and, alone among the recurrent
        mixers, takes ``positions`` (RoPE on q and k); its one position on a
        state is ``pallas/retention_step.py`` with ``state_kernel``, else
        ``ret_step``. An ``mla`` layer returns ``(h, latent, None)``,
        each position's latent row [b, t, r + dr]; its ``attention(q_nope, q_rope, latent) -> o``
        attends a cache of such rows instead of the block's own.

        In a model with learned sparse attention (``dsa``) an ``mla`` layer
        returns ``(h, latent, selection)``: a layer with an indexer selects
        (``dsa.select``: against its own index keys, or through
        ``indexer(q^I, k^I, w) -> selection`` against a cache of them), a
        layer without one takes ``selection``, the last selection made
        before it, and hands it on; ``attention`` then takes the selection
        as its fourth argument."""
        policy = self.policy
        b, t = h.shape[0], h.shape[1]
        u, maps = self._read(blk.get("hc1"), h)
        x = self._norm(u, blk["ln1"])
        if "kda" in blk or "mla" in blk or "gdn" in blk or "ret" in blk:
            if sequence_parallel:
                raise NotImplementedError(
                    "sequence parallelism is written for 'attn' layers only")
            if "kda" in blk:
                y, k, v = kda_mod.kda_mixer(
                    x, blk["kda"], num_heads=self.num_heads,
                    lower=self.kda["lower"], cast=policy.cast_compute,
                    live=live, state=state, kernel=state_kernel)
            elif "gdn" in blk:
                y, k, v = gdn_mod.gdn_mixer(
                    x, blk["gdn"], dims=self.gdn, eps=self.norm_eps,
                    cast=policy.cast_compute, live=live, state=state,
                    kernel=state_kernel)
            elif "ret" in blk:
                # the first recurrent mixer that takes positions: RoPE
                y, k, v = ret_mod.ret_mixer(
                    x, blk["ret"], heads=self.num_heads,
                    kv_heads=self.num_kv_heads,
                    rope=lambda a, at: self._rope_head(a, at, layer),
                    rmsnorm=lambda a, g: _rmsnorm(a, g, self.norm_eps),
                    positions=jnp.arange(t) if positions is None
                    else positions, cast=policy.cast_compute, live=live,
                    state=state, kernel=state_kernel)
            else:
                y, k, v = self._mla(blk["mla"], x, attention, positions,
                                    train, indexer, selection)
            return self._ffn(blk, self._write(h, y, maps), live, moe_info,
                             train), k, v
        # ``attn.proj`` is closed wherever the attention core is called and
        # opened again for the output projection: a scope open round a
        # Pallas kernel would rename it in the trace (``scopes.py``)
        sizes = self.attn or {}
        per_head = bool(sizes.get("head_norm"))
        gate = None
        with scope("attn.proj"):
            q = x @ policy.cast_compute(blk["attn"]["wq"])
            if self.qk_norm and not per_head:
                q = _rmsnorm(q, blk["attn"]["q_norm"]["g"])
            q = q.reshape(b, t, self.num_heads, -1)
            if sizes.get("gate"):   # per head: [query ; gate] in wq's columns
                q, gate = jnp.split(q, 2, axis=-1)
            k = x @ policy.cast_compute(blk["attn"]["wk"])
            if self.qk_norm and not per_head:
                k = _rmsnorm(k, blk["attn"]["k_norm"]["g"])
            k = k.reshape(b, t, self.num_kv_heads, -1)
            if per_head:
                q = _rmsnorm(q, blk["attn"]["q_norm"]["g"], self.norm_eps)
                k = _rmsnorm(k, blk["attn"]["k_norm"]["g"], self.norm_eps)
            v = (x @ policy.cast_compute(blk["attn"]["wv"])).reshape(
                b, t, self.num_kv_heads, -1)
            if self.pos_encoding == "rope":
                if positions is None:
                    positions = jnp.arange(t)
                q, k = (self._rope_head(a, positions, layer)
                        for a in (q, k))
        # the returned k/v stay at num_kv_heads (what the KV cache
        # stores); attention sees them repeated per query-head group
        window = self._window(layer)
        if attention is not None:
            o = attention(q, k, v)
        elif sequence_parallel and mesh is not None:
            if self.sp_impl == "ulysses":
                from deeplearning4j_tpu.parallel.ulysses import (
                    ulysses_attention)

                o = ulysses_attention(
                    q, self._repeat_kv(k), self._repeat_kv(v), mesh,
                    causal=True, window=window)
            else:
                o = ring_attention(q, self._repeat_kv(k),
                                   self._repeat_kv(v), mesh, causal=True,
                                   impl=self._attn_impl(t, train=train),
                                   window=window)
        elif self._attn_impl(t, train=train) == "flash":
            o = flash_attention(q, self._repeat_kv(k), self._repeat_kv(v),
                                causal=True, window=window)
        else:
            # grouped attention broadcasts each kv head over its query
            # group — no materialized repeat (= dot_product_attention
            # when H == Hkv)
            o = grouped_query_attention(q, k, v, causal=True,
                                        window=window)
        with scope("attn.proj"):
            if gate is not None:
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(x.dtype)
            if maps is None:
                h = h + o.reshape(b, t, -1) @ policy.cast_compute(
                    blk["attn"]["wo"])
            else:
                o = o.reshape(b, t, -1) @ policy.cast_compute(
                    blk["attn"]["wo"])
        if maps is not None:
            h = self._write(h, o, maps)
        return self._ffn(blk, h, live, moe_info, train), k, v

    def _read(self, p, h):
        """A sub-layer's input from the residual ``h`` and what its output
        is written back through: ``(h, None)`` on the plain residual; with
        hyper-connections (``p``: the sub-layer's ``hc`` parameters, ``h``
        [b, t, n, D]) ``(H_pre X, (H_post, H_res))`` (``models/hc.py``)."""
        if p is None:
            return h, None
        pre, post, res = hc_mod.maps(h, p, dims=self.hc,
                                     norm_eps=self.norm_eps)
        return hc_mod.read(h, pre), (post, res)

    def _write(self, h, y, maps):
        """The residual after a sub-layer's output ``y``: ``h + y``, or
        ``H_res X + H_post^T y`` through ``_read``'s maps."""
        return h + y if maps is None else hc_mod.write(h, y, *maps)

    def _enter(self, h):
        """The embedding [b, t, D] as the stack's residual: itself, or with
        hyper-connections a copy in each stream, [b, t, n, D]."""
        return hc_mod.expand(h, int(self.hc["streams"])) if self.hc else h

    @property
    def by_layer(self) -> bool:
        """True when ``attn=`` describes the 'attn' layers one by one: a
        window a layer or a RoPE a layer kind. ``_block`` then needs its
        ``layer``."""
        return bool(self.attn and (self.attn.get("windows") is not None
                                   or self.attn.get("rope")))

    def _window(self, layer: Optional[int]) -> Optional[int]:
        """The sliding window of 'attn' layer ``layer`` (None: it attends
        its whole prefix): the model's one ``attn_window`` unless
        ``attn['windows']`` gives one a layer."""
        if layer is None:
            if self.by_layer:
                raise ValueError(
                    "this model's 'attn' layers are described one by one "
                    "(attn['windows'], attn['rope']): _block needs layer=")
            return self.attn_window
        return self.windows[layer]

    def _rope_head(self, x, positions, layer: Optional[int] = None):
        """RoPE on the first ``rotary_dim`` dimensions of each head of ``x``
        [b, t, H, Dh] (all of them unless ``attn=`` says fewer), with the
        base and scaling of the layer's kind where ``attn['rope']`` names
        it ('window' or 'full', by ``_window``), else the model's."""
        r = self.rotary_dim
        kind = "full" if self._window(layer) is None else "window"
        theta, scaling = self.rope_by_kind.get(
            kind, (self.rope_theta, self.rope_scaling))
        turned = _rope(x[..., :r], positions, theta,
                       self.rope_interleaved, scaling)
        return turned if r == x.shape[-1] else jnp.concatenate(
            [turned, x[..., r:]], axis=-1)

    def _ffn(self, blk, h, live, moe_info, train=False):
        """The block's second half on the residual stream ``h`` [b, t, D]:
        the norm and the layer's feed-forward (``blk`` holds ``moe``,
        ``glu`` or ``mlp``). ``train``: the trace takes a gradient."""
        policy = self.policy
        b, t = h.shape[0], h.shape[1]
        u, maps = self._read(blk.get("hc2"), h)
        x = self._norm(u, blk["ln2"])
        limit = self.swiglu_limit
        if "moe" in blk:
            m = self.moe
            y, info = routed_experts.routed_ffn(
                x.reshape(b * t, -1), blk["moe"],
                experts_per_token=self.experts_per_token,
                norm_topk_prob=self.norm_topk_prob,
                cast=policy.cast_compute,
                live=None if live is None else live.reshape(b * t),
                groups=(m["n_group"], m["topk_group"], m["scale"])
                if m and "n_group" in m else None,
                first=m["first"] if m else 0, train=train, limit=limit)
            if moe_info is not None:
                moe_info.append(info)
            return self._write(h, y.reshape(b, t, -1), maps)
        # on the plain residual each form keeps its own order of ops (the
        # add inside the scope, the bias after it): the lowered programs of
        # the models without ``hc`` or a limit are letter for letter the same
        with scope("ffn.dense"):
            if "glu" in blk:
                g = blk["glu"]
                if limit:
                    x = routed_experts.swiglu(
                        x @ policy.cast_compute(g["w1"]),
                        x @ policy.cast_compute(g["w3"]), limit)
                else:
                    x = (jax.nn.silu(x @ policy.cast_compute(g["w1"]))
                         * (x @ policy.cast_compute(g["w3"])))
                y = x @ policy.cast_compute(g["w2"])
                if maps is None:
                    return h + y
            else:
                x = jax.nn.gelu(x @ policy.cast_compute(blk["mlp"]["w1"])
                                + policy.cast_compute(blk["mlp"]["b1"]))
                if maps is None:
                    return (h + x @ policy.cast_compute(blk["mlp"]["w2"])
                            + policy.cast_compute(blk["mlp"]["b2"]))
                y = (x @ policy.cast_compute(blk["mlp"]["w2"])
                     + policy.cast_compute(blk["mlp"]["b2"]))
        return self._write(h, y, maps)

    def _mla(self, p, x, attention, positions, train, indexer=None,
             selection=None):
        """The latent-attention mixer on the normed ``x`` [b, t, D]:
        ``(y [b, t, D], latent [b, t, r + dr], selection)``; ``selection``
        is None unless the model has learned sparse attention, where it is
        what this layer attended (``_block``). Without ``attention``
        the positions attend each other causally, keys and values expanded
        from the latents: the XLA op, or the flash kernel where
        ``_attn_impl`` says so. The kernel takes one head size, so q and k
        (dn + dr = 192 wide) and v (128) are padded with zeros to the next
        multiple of 128: the scores and the first dv columns of the result
        are unchanged."""
        cast = self.policy.cast_compute
        t = x.shape[1]
        if positions is None:
            positions = jnp.arange(t)

        def rope(a):
            return _rope(a, positions, self.rope_theta, self.rope_interleaved,
                         self.rope_scaling)

        def rmsnorm(a, g):
            return _rmsnorm(a, g, self.norm_eps)

        c_q = mla_mod.compress_query(x, p, rmsnorm=rmsnorm, cast=cast)
        q_nope, q_rope, latent, gate = mla_mod.mla_project(
            x, p, num_heads=self.num_heads, dims=self.mla, cast=cast,
            rope=rope, rmsnorm=rmsnorm, c_q=c_q)
        if "indexer" in p:
            iq, ik, iw = dsa_mod.index_project(
                x, c_q, p["indexer"], dims=self.dsa, rope=rope, cast=cast,
                layernorm=lambda a, g, b: _layernorm(a, g, b, self.norm_eps))
            if indexer is not None:
                selection = indexer(iq, ik, iw)
            else:
                pos = jnp.broadcast_to(positions, x.shape[:2])
                pool = self.dsa.get("pool", 1)
                if pool > 1:    # the block's own keys, pooled
                    selection = dsa_mod.select(
                        iq, iw, dsa_mod.pool_keys(ik, pool), pos,
                        self.dsa["topk"], pool=pool)
                    if not isinstance(selection, tuple):
                        selection = selection[..., :t]  # whole pools -> t
                else:
                    selection = dsa_mod.select(iq, iw, ik, pos,
                                               self.dsa["topk"])
        if selection is not None:
            # each query attends its selected rows, absorbed: the block's
            # own latents unless ``attention`` holds a cache of them
            o = (attention(q_nope, q_rope, latent, selection)
                 if attention is not None else dsa_mod.attend_selected(
                     q_nope, q_rope, latent, selection, p, dims=self.mla,
                     cast=cast))
            return mla_mod.mla_output(o, gate, p, cast), latent, selection

        def causal(q, k, v, scale):
            if self._attn_impl(t, train=train) != "flash":
                return dot_product_attention(q, k, v, causal=True,
                                             scale=scale)
            d = -(-q.shape[-1] // 128) * 128

            def pad(a):
                return jnp.pad(a, ((0, 0),) * 3 + ((0, d - a.shape[-1]),))

            return flash_attention(pad(q), pad(k), pad(v), causal=True,
                                   scale=scale)[..., :v.shape[-1]]

        if attention is not None:
            o = attention(q_nope, q_rope, latent)
        else:
            o = mla_mod.attend_full(q_nope, q_rope, latent, p, dims=self.mla,
                                    attention=causal, cast=cast)
        return mla_mod.mla_output(o, gate, p, cast), latent, None

    def _norm(self, x, p):
        """The model's norm (``norm=``) with the parameters ``p``."""
        if self.norm == "rmsnorm":
            return _rmsnorm(x, p["g"], self.norm_eps)
        return _layernorm(x, p["g"], p["b"], self.norm_eps)

    @property
    def hybrid(self) -> bool:
        """True when some layer's mixer is not key/value attention."""
        return any(m != "attn" for m in self.mixers)

    def layers_of(self, kind: str):
        """The indices of the layers whose mixer or feed-forward is
        ``kind``, in order: a layer's place in its kind's own state
        (``serving/kv_cache.py``) is its place in this list."""
        return [i for i, pair in enumerate(zip(self.mixers, self.ffns))
                if kind in pair]

    def n_layers(self, kind: str) -> int:
        """How many blocks of ``kind`` keep state or route here: the layers
        and, in a model with one, the multi-token-prediction module's block
        (of the last layer's kind), which comes after them in its kind's
        state and in a serving program's routing."""
        last = (self.mixers[-1], self.ffns[-1])
        return len(self.layers_of(kind)) + bool(self.mtp and kind in last)

    @property
    def experts_held(self) -> int:
        """Routed experts a layer holds here (all of them unless ``moe``
        names a share)."""
        return int(self.moe["held"]) if self.moe else self.num_experts

    def _repeat_kv(self, x):
        """[b, t, Hkv, d] → [b, t, H, d] by repeating each kv head over
        its query-head group (no-op when H == Hkv)."""
        rep = self.num_heads // self.num_kv_heads
        with scope("attn.proj"):
            return x if rep == 1 else jnp.repeat(x, rep, axis=2)

    def forward(self, params, tokens, *, mesh: Optional[Mesh] = None,
                sequence_parallel: bool = False, train: bool = False,
                moe_info: Optional[list] = None):
        """tokens: [b, t] int32 → logits [b, t, V]. ``train=True`` is the
        training hot path: "auto" attention resolves to the flash kernel
        whenever head_dim tiles (see ``_attn_impl``). A list passed as
        ``moe_info`` receives each layer's routing (``_block``; rows are
        the b·t tokens; not under ``remat`` or ``scan_layers``, whose
        bodies are traced apart)."""
        h = self._hidden(params, tokens, mesh=mesh,
                         sequence_parallel=sequence_parallel, train=train,
                         moe_info=moe_info)
        logits = self._unembed(params, h)
        with scope("lm.head"):
            return self.policy.cast_output(logits)

    def _hidden(self, params, tokens, *, mesh=None, sequence_parallel=False,
                train=False, moe_info=None):
        """``forward`` up to the last block: [b, t, D], before the final
        norm."""
        if moe_info is not None and (self.remat or self.scan_layers):
            raise ValueError("moe_info needs remat=False, scan_layers=False")
        if self.scan_layers and (self.by_layer or max(map(len, map(set, (
                self.mixers, self.ffns, self.indexers)))) > 1):
            raise ValueError("scan_layers needs every layer the same block")
        policy = self.policy
        b, t = tokens.shape
        with scope("lm.embed"):
            h = jnp.take(params["embed"], tokens, axis=0)
            if self.pos_encoding == "learned":
                h = h + params["pos"][:t][None]
            h = policy.cast_compute(h)
        h = self._enter(h)

        def block_fn(blk, h, selection=None, layer=None):
            return self._block(blk, h, mesh=mesh,
                               sequence_parallel=sequence_parallel,
                               train=train, moe_info=moe_info,
                               selection=selection, layer=layer)

        if self.remat:
            # ``layer`` picks a window and a RoPE at trace time: static
            block_fn = jax.checkpoint(
                block_fn, static_argnums=(3,) if self.by_layer else ())
        if self.scan_layers:
            # one scan over the stacked per-layer params: the traced
            # program holds ONE block body however deep the net is
            # (outputs match the loop path — asserted <= 1e-6 in
            # tests/test_models.py; exact equality is not promised
            # because XLA schedules the scan body independently)
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *params["blocks"])
            h, _ = lax.scan(lambda c, blk: (block_fn(blk, c)[0], None),
                            h, stacked)
        else:
            # a selection of key positions is the one value that goes from
            # a layer to the layers after it beside the residual stream
            selection = None
            for i, blk in enumerate(params["blocks"]):
                # a model described by layer says which layer this is
                h, _, left = block_fn(blk, h, selection,
                                      *((i,) if self.by_layer else ()))
                if self.dsa and "mla" in blk:
                    selection = left
        return h

    def mtp_input(self, params, g, tokens):
        """What the module's block reads (DeepSeek-V3 eq. 21): ``h'_i =
        [rmsnorm_e(Emb(t_{i+1})) ; rmsnorm_h(g_i)] M`` for ``g`` [..., D],
        the model's hidden states after its final norm, and ``tokens``
        [...], the tokens one position on. Scopes ``mtp.embed`` and
        ``mtp.proj``."""
        policy, m = self.policy, params["mtp"]
        with scope("mtp.embed"):
            e = policy.cast_compute(jnp.take(params["embed"], tokens, axis=0))
        with scope("mtp.proj"):
            both = jnp.concatenate([self._norm(e, m["enorm"]),
                                    self._norm(g, m["hnorm"])], axis=-1)
            return both @ policy.cast_compute(m["proj"])

    def mtp_block(self, params, x, **kw):
        """The module's own block on ``mtp_input``'s ``x`` [b, t, D], as
        ``_block`` runs a layer (``kw``: its keywords), every scope inside
        it under ``mtp``. Returns ``_block``'s triple."""
        with scope("mtp"):
            return self._block(params["mtp"]["block"], x, **kw)

    def mtp_logits(self, params, g, tokens, **kw):
        """The module's logits [b, t, V]: at position i, from ``g[:, i]`` and
        ``tokens[:, i]`` = token i + 1, the distribution of token i + 2,
        through the model's own embedding and head."""
        h, _, _ = self.mtp_block(params, self.mtp_input(params, g, tokens),
                                 **kw)
        return self.mtp_head(params, h)

    def mtp_head(self, params, h):
        """The module's own norm and the model's head on the module's block
        output ``h`` [..., D] -> float32 logits [..., V]."""
        with scope("mtp"):
            return self._unembed(params, h, params["mtp"]["norm"])

    @traced
    def loss(self, params, tokens, *, mesh=None, sequence_parallel=False,
             train: bool = False):
        """Next-token cross entropy (mean over positions); with a
        multi-token-prediction module, plus ``mtp["loss_weight"]`` times the
        module's cross entropy against the tokens two positions on
        (DeepSeek-V3 eq. 24-25, depth 1)."""
        h = self._hidden(params, tokens, mesh=mesh,
                         sequence_parallel=sequence_parallel, train=train)

        def mean_nll(logits, targets):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return jnp.mean(-jnp.take_along_axis(
                logp, targets[..., None], axis=-1))

        logits = self._unembed(params, h)
        with scope("lm.head"):
            logits = self.policy.cast_output(logits)
            targets = tokens[:, 1:]     # the parent's order, op for op
            total = mean_nll(logits[:, :-1], targets)
        if self.mtp and tokens.shape[1] > 2:
            g = self._norm(h, params["ln_f"])[:, :-2]
            extra = self.mtp_logits(params, g, tokens[:, 1:-1], train=train)
            with scope("lm.head"):
                total = total + self.mtp["loss_weight"] * mean_nll(
                    extra, tokens[:, 2:])
        return total

    # ------------------------------------------------------------------
    @traced
    def _step_body(self, *, mesh: Optional[Mesh] = None,
                   sequence_parallel: bool = False):
        """Un-jitted single optimizer step (shared by the per-step jit and
        the fused multi-step scan). Under the ``mixed_bf16``
        master-weights policy the step derives ONE bf16 parameter copy
        for forward/backward, upcasts the bf16 grads once, and applies
        Adam to the carried f32 masters + f32 moments — the standard
        f32-state/bf16-compute split; per-matmul ``cast_compute`` calls
        inside ``_block`` become no-ops on the copy's leaves."""
        lr = self.lr
        b1, b2, eps = 0.9, 0.999, 1e-8

        def step(params, opt_state, tokens, step_count):
            with scope("opt.cast"):
                fwd_params = self.policy.compute_copy(params)
            loss, grads = jax.value_and_grad(
                lambda p: self.loss(p, tokens, mesh=mesh,
                                    sequence_parallel=sequence_parallel,
                                    train=True)
            )(fwd_params)
            with scope("opt.cast"):
                grads = self.policy.master_grads(grads)
            t = step_count.astype(jnp.float32) + 1.0

            def upd(p, g, s):
                m = b1 * s["m"] + (1 - b1) * g
                v = b2 * s["v"] + (1 - b2) * g * g
                mhat = m / (1 - b1 ** t)
                vhat = v / (1 - b2 ** t)
                return (p - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype), \
                    {"m": m, "v": v}

            flat_p, treedef = jax.tree_util.tree_flatten(params)
            flat_s = treedef.flatten_up_to(opt_state)
            flat_g = treedef.flatten_up_to(grads)
            with scope("opt.update"):
                out = [upd(p, g, s)
                       for p, g, s in zip(flat_p, flat_g, flat_s)]
            new_params = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
            new_state = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
            return new_params, new_state, loss

        return step

    def make_train_step(self, *, mesh: Optional[Mesh] = None,
                        sequence_parallel: bool = False, donate: bool = True):
        ensure_compile_cache()
        step = self._step_body(mesh=mesh, sequence_parallel=sequence_parallel)
        return jax.jit(step, donate_argnums=(0, 1) if donate else ())

    def make_multi_train_step(self, k: int, *, mesh: Optional[Mesh] = None,
                              sequence_parallel: bool = False,
                              donate: bool = True):
        """K optimizer steps fused into ONE XLA program (``lax.scan`` over
        the shared step body): one host dispatch + one token transfer per
        K steps, isolating the chip from the per-dispatch floor."""
        ensure_compile_cache()
        step = self._step_body(mesh=mesh, sequence_parallel=sequence_parallel)

        def multi(params, opt_state, tokens, step_count):
            def body(carry, _):
                p, s, c = carry
                p, s, loss = step(p, s, tokens, c)
                return (p, s, c + 1), loss

            (p, s, _), losses = jax.lax.scan(
                body, (params, opt_state, step_count), None, length=k)
            return p, s, losses[-1]

        return jax.jit(multi, donate_argnums=(0, 1) if donate else ())

    def fit_batch(self, tokens, train_step=None, block: bool = True):
        """``block=False`` returns the on-device loss scalar without a
        host round-trip, letting steps pipeline (read it when needed).

        Spans: ``train.step`` (``step``) from the step program's dispatch
        to the return, and inside it, with ``block=True`` only,
        ``train.sync`` round the wait for the loss."""
        if self.params is None:
            self.init()
        train_step = train_step or self._default_step
        with tracer().span("train.step", step=self.step_count):
            self.params, self.opt_state, loss = train_step(
                self.params, self.opt_state, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(self.step_count, jnp.int32))
            self.step_count += 1
            if not block:
                return loss
            with tracer().span("train.sync"):
                return float(loss)

    def fit_batch_multi(self, tokens, *, multi_step, k: int,
                        block: bool = True):
        """Run a fused K-step program (see ``make_multi_train_step``)."""
        if self.params is None:
            self.init()
        self.params, self.opt_state, loss = multi_step(
            self.params, self.opt_state, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(self.step_count, jnp.int32))
        self.step_count += k
        return float(loss) if block else loss

    @functools.cached_property
    def _default_step(self):
        return self.make_train_step()

    # ------------------------------------------------------------------
    # serialization
    # ------------------------------------------------------------------
    def get_config(self) -> Dict[str, Any]:
        """Constructor kwargs sufficient to rebuild this model —
        ``TransformerLM(**lm.get_config())`` (the checkpoint's
        configuration.json; role of the DSL conf for the zoo networks)."""
        return {
            "vocab_size": self.vocab_size, "d_model": self.d_model,
            "num_heads": self.num_heads, "num_layers": self.num_layers,
            "num_kv_heads": self.num_kv_heads,
            "attn_window": self.attn_window,
            "d_ff": self.d_ff, "max_len": self.max_len, "lr": self.lr,
            "seed": self.seed, "dtype_policy": self.dtype_policy_name,
            "attn_impl": self.attn_impl, "remat": self.remat,
            "pos_encoding": self.pos_encoding,
            "scan_layers": self.scan_layers,
            "norm": self.norm, "qk_norm": self.qk_norm,
            "num_experts": self.num_experts,
            "experts_per_token": self.experts_per_token,
            "norm_topk_prob": self.norm_topk_prob,
            "tie_embeddings": self.tie_embeddings,
            "rope_theta": self.rope_theta,
            "rope_interleaved": self.rope_interleaved,
            "norm_eps": self.norm_eps,
            "mixers": list(self.mixers), "ffns": list(self.ffns),
            "glu_width": self.glu_width, "kda": self.kda, "mla": self.mla,
            "moe": self.moe, "indexers": list(self.indexers),
            "dsa": self.dsa, "rope_scaling": self.rope_scaling,
            "mtp": self.mtp, "gdn": self.gdn, "attn": self.attn,
            "ret": self.ret, "hc": self.hc,
        }

    def _ensure_init(self):
        if self.params is None:
            self.init()

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------
    def evaluate_perplexity(self, tokens) -> float:
        """Corpus perplexity ``exp(mean next-token NLL)`` over [b, t]
        token batches (the LM analogue of ``Evaluation.stats`` accuracy:
        eval/Evaluation.java:90-147 evaluates classifiers; an LM's
        standard metric is perplexity)."""
        if self.params is None:
            self.init()
        return float(jnp.exp(self._loss_jit(
            self.params, jnp.asarray(tokens, jnp.int32))))

    @functools.cached_property
    def _loss_jit(self):
        return jax.jit(self.loss)

    # ------------------------------------------------------------------
    # autoregressive decoding (KV cache)
    # ------------------------------------------------------------------
    def _unembed(self, params, h, ln=None):
        """Final norm (``ln``: another norm's parameters, the
        multi-token-prediction module's) + unembedding (the embedding
        itself when tied,
        else the ``head`` leaf) on [..., D] hidden → [..., V] f32 logits.
        The matmul runs with compute-dtype (bf16) operands and f32
        accumulation — one of the largest matmuls in the step, so a
        plain f32 matmul here would cost MXU rate."""
        policy = self.policy
        if self.hc:     # the exit: the streams' sum, [..., n, D] -> [..., D]
            h = hc_mod.collapse(h)
        with scope("lm.head"):
            hf = self._norm(h, params["ln_f"] if ln is None else ln)
            head = params["embed" if self.tie_embeddings else "head"]
            return lax.dot_general(
                policy.cast_compute(hf), policy.cast_compute(head),
                (((hf.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    def _prefill(self, params, prompt, max_new_tokens: int):
        """One parallel forward over the prompt capturing per-layer K/V.
        Returns ``(h_last [b, D], cache)`` with cache entries padded out
        to ``prompt_len + max_new_tokens`` positions."""
        if set(self.mixers) - {"attn", "ret"}:
            raise NotImplementedError(
                "generate() and generate_beam() carry key/value caches and a "
                "'ret' layer's state only: a 'kda' or 'gdn' layer's "
                "recurrent state and convolution tail, an 'mla' layer's "
                "latent rows and an indexer's keys are held by "
                "serving.DecodeServer's slot cache")
        policy = self.policy
        cdt = policy.compute_dtype
        prompt_len = prompt.shape[1]
        with scope("lm.embed"):
            h = jnp.take(params["embed"], prompt, axis=0)
            if self.pos_encoding == "learned":
                h = h + params["pos"][:prompt_len][None]
            h = policy.cast_compute(h)
        h = self._enter(h)
        cache = []
        pad_t = ((0, 0), (0, max_new_tokens), (0, 0), (0, 0))
        for i, blk in enumerate(params["blocks"]):
            h, kk, vv = self._block(blk, h, layer=i)
            if "ret" in blk:    # no rows a position: the state and its norm
                cache.append({"s": kk, "z": vv})
                continue
            cache.append({"k": jnp.pad(kk.astype(cdt), pad_t),
                          "v": jnp.pad(vv.astype(cdt), pad_t)})
        return h[:, -1], cache

    def _decode_token(self, params, cache, tok, t, total: int):
        """Consume one token per row at position ``t`` (traced) against
        the cache, through the SAME ``_block`` math as training/prefill —
        only the attention core differs. Returns ``(h_last, new_cache)``."""
        policy = self.policy
        cdt = policy.compute_dtype
        B = tok.shape[0]
        with scope("lm.embed"):
            h = jnp.take(params["embed"], tok, axis=0)
            if self.pos_encoding == "learned":
                h = h + params["pos"][t]
            h = policy.cast_compute(h)[:, None, :]          # [B, 1, D]
        h = self._enter(h)
        new_cache = []
        masks = {}      # {window: [1, total] keys a query at t may see}

        def cached_attention(c, window):
            if window not in masks:
                live = jnp.arange(total) <= t               # [total]
                if window is not None:
                    live &= jnp.arange(total) > t - window
                masks[window] = live[None, :]
            live = masks[window]

            def attn(q, kk, vv):
                ck = lax.dynamic_update_slice(
                    c["k"], kk.astype(cdt), (0, t, 0, 0))
                cv = lax.dynamic_update_slice(
                    c["v"], vv.astype(cdt), (0, t, 0, 0))
                new_cache.append({"k": ck, "v": cv})
                return grouped_query_attention(
                    q, ck, cv, mask=jnp.broadcast_to(live, (B, total)))
            return attn

        for i, (blk, c) in enumerate(zip(params["blocks"], cache)):
            if "ret" in blk:
                h, s, z = self._block(blk, h, state=(c["s"], c["z"]),
                                      positions=jnp.asarray(t)[None], layer=i)
                new_cache.append({"s": s, "z": z})
                continue
            h, _, _ = self._block(
                blk, h, attention=cached_attention(c, self.windows[i]),
                positions=jnp.asarray(t)[None], layer=i)
        return h[:, 0], new_cache

    def _validate_decode_args(self, prompt_len, max_new_tokens):
        total = prompt_len + max_new_tokens
        if prompt_len < 1:
            raise ValueError("prompt_len must be >= 1")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # only the learned position TABLE bounds the context; RoPE has no
        # table and may decode past max_len (relative positions)
        if total > self.max_len and self.pos_encoding == "learned":
            raise ValueError(
                f"prompt_len + max_new_tokens = {total} exceeds "
                f"max_len={self.max_len} (learned position table; use "
                f"pos_encoding='rope' to decode past it)")
        return total

    def make_generate(self, prompt_len: int, max_new_tokens: int, *,
                      temperature: float = 0.0, top_k: Optional[int] = None):
        """Build a jitted ``gen(params, prompt, key) -> [b, total]`` decoder.

        The stateful-inference analogue of the reference's ``rnnTimeStep``
        (MultiLayerNetwork.java:1208 stateMap carry), TPU-first: the prompt
        prefills the KV cache with ONE batched forward (all positions in
        parallel through the shared block math), then a decode-only
        ``lax.scan`` emits one token per step against the static-shape
        cache (``lax.dynamic_update_slice``) — a single XLA program, no
        per-token dispatch. ``temperature=0`` decodes greedily; otherwise
        samples from ``softmax(logits/temperature)`` filtered to ``top_k``.
        """
        total = self._validate_decode_args(prompt_len, max_new_tokens)
        if top_k is not None and not 1 <= top_k <= self.vocab_size:
            raise ValueError(
                f"top_k={top_k} must be in [1, vocab_size={self.vocab_size}]")
        if temperature < 0.0:
            raise ValueError(f"temperature={temperature} must be >= 0")

        def sample(logits, key):
            if temperature == 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
            scaled = logits / temperature
            if top_k is not None:
                kth = lax.top_k(scaled, top_k)[0][:, -1]
                scaled = jnp.where(scaled >= kth[:, None], scaled, -jnp.inf)
            key, sub = jax.random.split(key)
            return jax.random.categorical(sub, scaled, axis=-1).astype(
                jnp.int32), key

        def gen(params, prompt, key):
            # ---- prefill: one parallel forward over the prompt
            h_last, cache = self._prefill(params, prompt, max_new_tokens)
            first, key = sample(self._unembed(params, h_last), key)

            # ---- decode: one token per scan step against the cache
            def step(carry, t):
                cache, tok, key = carry
                h_last, new_cache = self._decode_token(
                    params, cache, tok, t, total)
                nxt, key = sample(self._unembed(params, h_last), key)
                return (new_cache, nxt, key), nxt

            # steps consume generated tokens at positions p .. total-2,
            # each emitting the NEXT token; `first` is position p itself
            (_, _, _), rest = lax.scan(
                step, (cache, first, key),
                jnp.arange(prompt_len, total - 1))
            gen_tokens = jnp.concatenate([first[:, None], rest.T], axis=1)
            return jnp.concatenate(
                [prompt, gen_tokens.astype(prompt.dtype)], axis=1)

        return jax.jit(gen)

    def make_generate_beam(self, prompt_len: int, max_new_tokens: int,
                           beam_size: int):
        """Build a jitted ``gen(params, prompt) -> (seqs, scores)`` beam
        decoder: ``seqs`` [b, beam, prompt_len+max_new] (best beam first),
        ``scores`` [b, beam] summed token log-probs.

        Beam counterpart of the reference's ImageLSTM caption search
        (nn/layers/recurrent.py beam_search), on the KV cache: beams ride
        the batch dim ([b*beam] rows), each scan step extends every beam,
        takes the top ``beam_size`` of the b×(beam·V) candidates, and
        reorders cache rows by parent beam with one gather."""
        total = self._validate_decode_args(prompt_len, max_new_tokens)
        K, V = beam_size, self.vocab_size
        if not 1 <= K <= V:
            raise ValueError(f"beam_size={K} must be in [1, vocab={V}]")

        def gen(params, prompt):
            b = prompt.shape[0]
            h_last, cache = self._prefill(params, prompt, max_new_tokens)
            logp0 = jax.nn.log_softmax(self._unembed(params, h_last), -1)
            scores, tok0 = lax.top_k(logp0, K)              # [b, K]
            tok0 = tok0.astype(jnp.int32)
            # beams ride the batch dim, batch-major: row = batch*K + beam
            cache = jax.tree_util.tree_map(
                lambda a: jnp.repeat(a, K, axis=0), cache)
            seqs = jnp.zeros((b, K, max_new_tokens), jnp.int32)
            seqs = lax.dynamic_update_slice(
                seqs, tok0[:, :, None], (0, 0, 0))

            def step(carry, ti):
                cache, seqs, scores, prev = carry
                t, i = ti
                h_last, cache = self._decode_token(
                    params, cache, prev.reshape(b * K), t, total)
                logp = jax.nn.log_softmax(self._unembed(params, h_last), -1)
                cand = scores[:, :, None] + logp.reshape(b, K, V)
                new_scores, idx = lax.top_k(cand.reshape(b, K * V), K)
                parent = idx // V                            # [b, K]
                tok = (idx % V).astype(jnp.int32)
                rows = (jnp.arange(b)[:, None] * K + parent).reshape(-1)
                cache = jax.tree_util.tree_map(lambda a: a[rows], cache)
                seqs = jnp.take_along_axis(seqs, parent[..., None], axis=1)
                seqs = lax.dynamic_update_slice(
                    seqs, tok[:, :, None], (0, 0, i))
                return (cache, seqs, new_scores, tok), None

            ts = jnp.arange(prompt_len, total - 1)           # consumed pos
            slots = jnp.arange(1, max_new_tokens)            # written slot
            (cache, seqs, scores, _), _ = lax.scan(
                step, (cache, seqs, scores, tok0), (ts, slots))
            out = jnp.concatenate(
                [jnp.repeat(prompt[:, None], K, axis=1), seqs], axis=2)
            return out, scores

        return jax.jit(gen)

    # a serving loop with varying prompt lengths compiles one program per
    # (shape, sampling) signature; bound the cache so it cannot grow
    # without limit (LRU — jax's own executable cache keeps recently
    # evicted programs warm if the signature comes right back)
    GEN_CACHE_MAX = 16

    def _cached_decoder(self, sig, factory):
        """Lazy per-signature compile cache shared by the decode APIs
        (LRU-bounded at ``GEN_CACHE_MAX`` signatures)."""
        from collections import OrderedDict

        if self.params is None:
            self.init()
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = OrderedDict()
        fn = cache.get(sig)
        if fn is None:
            fn = cache[sig] = factory()
            while len(cache) > self.GEN_CACHE_MAX:
                cache.popitem(last=False)
        else:
            cache.move_to_end(sig)
        return fn

    def generate_beam(self, prompt, max_new_tokens: int, beam_size: int = 4):
        """Beam-search decode ``max_new_tokens`` past ``prompt`` ([b, t]).
        Returns ``(seqs [b, beam, t+max_new], scores [b, beam])``,
        best beam first. Compiled per (shape, beam) signature."""
        prompt = jnp.asarray(prompt, jnp.int32)
        fn = self._cached_decoder(
            ("beam", prompt.shape, max_new_tokens, beam_size),
            lambda: self.make_generate_beam(
                prompt.shape[1], max_new_tokens, beam_size))
        return fn(self.params, prompt)

    def generate(self, prompt, max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 seed: int = 0):
        """Decode ``max_new_tokens`` past ``prompt`` ([b, t] int32).
        Compiles one program per (shape, sampling) signature and caches it."""
        prompt = jnp.asarray(prompt, jnp.int32)
        fn = self._cached_decoder(
            (prompt.shape, max_new_tokens, temperature, top_k),
            lambda: self.make_generate(
                prompt.shape[1], max_new_tokens,
                temperature=temperature, top_k=top_k))
        return fn(self.params, prompt, jax.random.PRNGKey(seed))

    # ------------------------------------------------------------------
    # tensor-parallel sharding specs (Megatron split)
    # ------------------------------------------------------------------
    def param_specs(self, *, shard_data_embed: bool = False,
                    model_axis_size: Optional[int] = None,
                    mesh: Optional[Mesh] = None) -> Dict[str, Any]:
        if mesh is not None and model_axis_size is None:
            model_axis_size = dict(mesh.shape).get(MODEL_AXIS, 1)
        col = P(None, MODEL_AXIS)
        row = P(MODEL_AXIS, None)
        # the Megatron split shards whole heads per device; with GQA the
        # kv heads must tile the model axis or shards cut inside a head
        # and K/V regather defeats the split — replicate wk/wv then.
        # Whether that applies depends on the axis size, so GQA/MQA specs
        # REQUIRE it (pass model_axis_size or mesh; shard_params does) —
        # a silent column default could emit an in-head-splitting sharding.
        kv_col = col
        if self.num_kv_heads != self.num_heads and model_axis_size is None:
            raise ValueError(
                "param_specs with GQA/MQA needs model_axis_size= (or "
                f"mesh=): whether the {self.num_kv_heads} kv heads can be "
                "column-sharded depends on the model-axis size")
        if model_axis_size and self.num_kv_heads % model_axis_size:
            logger.warning(
                "GQA TP fallback: num_kv_heads=%d does not tile the "
                "model axis (size %d) — wk/wv stay REPLICATED (no TP "
                "memory/compute savings on the K/V projections; with "
                "MQA that is all of them)",
                self.num_kv_heads, model_axis_size)
            kv_col = P()
        def norm():
            return ({"g": P(), "b": P()} if self.norm == "layernorm"
                    else {"g": P()})

        blocks = []
        for mixer, ffn, indexer in zip(self.mixers, self.ffns,
                                       self.indexers):
            blk = {"ln1": norm(), "ln2": norm()}
            if self.hc:     # every chip holds the maps' parameters whole
                blk["hc1"] = {"phi": P(), "alpha": P(), "b": P()}
                blk["hc2"] = {"phi": P(), "alpha": P(), "b": P()}
            if mixer == "attn":
                blk["attn"] = {"wq": col, "wk": kv_col, "wv": kv_col,
                               "wo": row}
                if self.qk_norm or (self.attn or {}).get("head_norm"):
                    blk["attn"]["q_norm"] = {"g": P()}
                    blk["attn"]["k_norm"] = {"g": P()}
            elif mixer == "kda":
                # no Megatron split is written for the four other mixers:
                # every chip holds them whole
                rank = (self.kda or {}).get("gate_rank")
                chan = (self.kda or {}).get("out_gate") == "channel"
                blk["kda"] = {n: P() for n in (
                    "wq", "wk", "wv", "wb", "wo", "conv_q",
                    "conv_k", "conv_v", "a_log", "dt_bias")
                    + (("wa_down", "wa_up") if rank else ("wa",))
                    + (("wg_down", "wg_up") if chan else ("wg",))}
                blk["kda"]["o_norm"] = {"g": P()}
            elif mixer == "gdn":
                blk["gdn"] = {n: P() for n in (
                    "w_qkvz", "w_ba", "wo", "conv", "a_log", "dt_bias")}
                blk["gdn"]["o_norm"] = {"g": P()}
            elif mixer == "ret":
                blk["ret"] = {n: P() for n in (
                    "wq", "wk", "wv", "wo", "wg", "bg")}
                blk["ret"]["q_norm"] = {"g": P()}
                blk["ret"]["k_norm"] = {"g": P()}
            else:
                names = ["wdkv", "wukv", "wo"] + (
                    ["wq_a", "wq_b"] if self.mla.get("q_lora_rank")
                    else ["wq"]) + (["wg"] if self.mla.get("gate", True)
                                    else [])
                blk["mla"] = {n: P() for n in names}
                blk["mla"]["kv_norm"] = {"g": P()}
                if self.mla.get("q_lora_rank"):
                    blk["mla"]["q_norm"] = {"g": P()}
                if indexer == "full":
                    blk["mla"]["indexer"] = {
                        "wq": P(), "wk": P(), "ww": P(),
                        "k_norm": {"g": P(), "b": P()}}
            if ffn == "moe":
                # every chip holds all experts (of this share), each
                # split on its width like the dense MLP; the router is
                # replicated
                blk["moe"] = {"router": P(),
                              "w_gate": P(None, None, MODEL_AXIS),
                              "w_up": P(None, None, MODEL_AXIS),
                              "w_down": P(None, MODEL_AXIS, None)}
                if self.moe and self.moe.get("bias"):
                    blk["moe"]["bias"] = P()
                if self.moe and self.moe.get("shared_width"):
                    blk["moe"]["shared"] = {"w_gate": col, "w_up": col,
                                            "w_down": row}
                    if self.moe.get("shared_gate"):
                        blk["moe"]["shared"]["gate"] = P()
            elif ffn == "glu":
                blk["glu"] = {"w1": col, "w3": col, "w2": row}
            else:
                blk["mlp"] = {"w1": col, "b1": P(MODEL_AXIS), "w2": row,
                              "b2": P()}
            blocks.append(blk)
        embed = row if shard_data_embed else P()
        specs = {"embed": embed, "ln_f": norm(), "blocks": blocks}
        if self.pos_encoding == "learned":
            specs["pos"] = P()
        if not self.tie_embeddings:
            specs["head"] = embed
        if self.mtp:
            specs["mtp"] = {"enorm": norm(), "hnorm": norm(), "proj": P(),
                            "block": blocks[-1], "norm": norm()}
        return specs

    def shard_params(self, mesh: Mesh, specs: Optional[Dict[str, Any]] = None):
        """Place params + opt state on the mesh with TP shardings.

        PartitionSpec is a tuple subclass, so tree_map would descend into it;
        flatten the params treedef and match specs leaf-for-leaf instead."""
        from deeplearning4j_tpu.parallel.sharding_registry import named

        specs = specs or self.param_specs(
            model_axis_size=dict(mesh.shape).get(MODEL_AXIS, 1))
        flat_p, treedef = jax.tree_util.tree_flatten(self.params)
        flat_spec = treedef.flatten_up_to(specs)
        self.params = jax.tree_util.tree_unflatten(treedef, [
            jax.device_put(p, named(mesh, s))
            for p, s in zip(flat_p, flat_spec)
        ])
        flat_s, sdef = jax.tree_util.tree_flatten(self.opt_state)
        # opt state nests {m, v} one level below each param leaf: repeat each
        # param spec twice in flatten order (dict keys sort: m, v)
        flat_sspec = [s for s in flat_spec for _ in range(2)]
        self.opt_state = jax.tree_util.tree_unflatten(sdef, [
            jax.device_put(p, named(mesh, s))
            for p, s in zip(flat_s, flat_sspec)
        ])
