"""Dropless routed experts: the sparse feed-forward of a mixture-of-experts
block (OLMoE, arXiv:2409.02060), as ``TransformerLM._block`` runs it in
training, prefill and decode.

    p  = softmax_float32(x Wr)                       Wr [D, E]
    (w_1..w_k, e_1..e_k) = top_k(p)                  raw probabilities, or
                                                     renormalised to sum 1
    y  = sum_j w_j * ((silu(x Wgate[e_j]) * (x Wup[e_j])) Wdown[e_j])

Two guarantees the serving engine leans on:

- **dropless** — every token is served by every expert it chose; there is
  no capacity, so a token's output depends on that token alone. Pad tails
  of a prompt bucket, free slots and the other requests of a batch change
  no row (``parallel/expert_parallel.moe_ffn`` is the capacity-limited
  GShard form; nothing here uses it).
- **float32 router** — logits, softmax and top-k run in float32 at
  ``highest`` matmul precision whatever the dtype policy: bf16 cannot tell
  experts apart whose logits differ by less than 2^-8 of their size.

The experts take one of two forms, chosen from the token count at trace
time (``DENSE_MAX_TOKENS``):

- few tokens (a decode step, a prompt of up to a thousand tokens): every
  expert runs on every token as one batched matmul and unchosen results
  get weight zero. At these counts the step waits for the expert weights,
  which both forms read, and the extra products hide under that read; no
  sort, no gather, no second copy of the weights.
- many tokens (a long prompt, a training batch): the (token, expert)
  pairs are sorted by expert and the three projections are grouped
  matmuls (``jax.lax.ragged_dot``: each row meets only its expert's
  weights), then each token gathers its k results back. Rows that hold no
  token (``live`` false) sort behind the last group and reach no expert.

A layer may describe its router and its share (``TransformerLM``'s ``moe=``):

- **group-limited sigmoid routing** (DeepSeek-V3's ``noaux_tc``,
  arXiv:2412.19437 section 2.1.2): scores ``s = sigmoid(x Wr)``; an expert
  bias ``b`` is added for choosing only; the experts lie in ``n_group`` runs
  of equal length, a group's score is the sum of its two largest ``s + b``,
  the ``topk_group`` best groups stay, and the k largest ``s + b`` among
  their experts are chosen; the weights are the chosen experts' unbiased
  ``s``, normalised to sum 1 and scaled (``route(..., groups=)``).
- **the experts held here**: of the router's E experts this chip holds
  ``held`` starting at ``first`` (the stacked matrices have ``held`` rows).
  The router keeps its E outputs and its k a token; the layer adds the
  chosen experts it holds and leaves out what the others would add. Nothing
  stands in for the absent chips. ``info["load"]`` is over the held experts.
- **a shared expert**: one more SwiGLU expert that every token passes
  through with weight 1 (``p["shared"]``), every chip alike.

Scopes ``moe.route``, ``moe.experts`` and ``moe.shared`` name the parts in a
device trace.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["DENSE_MAX_TOKENS", "init_experts", "route", "routed_ffn"]

# Up to this many tokens every expert runs on every token. Both forms
# read all the expert weights of a layer; the dense form's E/k-fold surplus
# of products hides under that read up to a few hundred rows, and the
# sorted form first writes a compute-dtype copy of the layer's experts for
# ``ragged_dot`` (the dense matmuls fuse the cast). On a v5e at OLMoE's
# widths with float32 storage (PERF.md section 6, PR 25): 32 rows 2.4 ms a
# layer dense against 5.3 sorted, 1,024 rows 4.9 against 7.6, 2,048 rows
# 9.9 against 9.7, where the dense form's intermediates are also 1.3 GiB.
DENSE_MAX_TOKENS = 1024


def init_experts(key, d_model: int, d_ff: int, num_experts: int, dtype, *,
                 held: Optional[int] = None, bias: bool = False,
                 shared_width: int = 0):
    """Glorot-normal router and stacked expert matrices: ``router``
    [D, E], ``w_gate`` / ``w_up`` [held, D, F], ``w_down`` [held, F, D]
    (``held`` defaults to E: every expert is here). ``bias``: an expert
    bias ``bias`` [E] for choosing (normal x 0.01: small, and not zero, so
    that a seeded model exercises it). ``shared_width`` > 0: a shared
    expert ``shared.{w_gate, w_up, w_down}`` of that width."""
    kr, kg, ku, kd = jax.random.split(key, 4)

    def glorot(k, shape, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dtype)
        return jax.random.normal(k, shape, dtype) * scale

    e, d, f = num_experts, d_model, d_ff
    n = e if held is None else held
    p = {"router": glorot(kr, (d, e), d, e),
         "w_gate": glorot(kg, (n, d, f), d, f),
         "w_up": glorot(ku, (n, d, f), d, f),
         "w_down": glorot(kd, (n, f, d), f, d)}
    if bias:
        p["bias"] = jax.random.normal(
            jax.random.fold_in(kr, 1), (e,), dtype) * 0.01
    if shared_width:
        ks = jax.random.split(jax.random.fold_in(kd, 1), 3)
        w = shared_width
        p["shared"] = {"w_gate": glorot(ks[0], (d, w), d, w),
                       "w_up": glorot(ks[1], (d, w), d, w),
                       "w_down": glorot(ks[2], (w, d), w, d)}
    return p


def route(x, router, experts_per_token: int, norm_topk_prob: bool = False,
          *, bias=None, groups: Optional[Tuple[int, int, float]] = None):
    """``x [N, D]``, ``router [D, E]`` -> ``(weights [N, k] float32,
    experts [N, k] int32)``: the k largest of the float32 softmax over
    all E experts, largest first (ties to the lower index).

    ``groups = (n_group, topk_group, scale)`` selects the group-limited
    sigmoid form instead (the module's docstring): ``bias`` [E] takes part
    in choosing groups and experts and in no weight."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if groups is not None:
        n_group, topk_group, scale = groups
        scores = jax.nn.sigmoid(logits)
        choose = scores if bias is None else scores + bias.astype(
            jnp.float32)
        n = choose.shape[0]
        grouped = choose.reshape(n, n_group, -1)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
        _, best = lax.top_k(group_score, topk_group)        # [N, topk_group]
        kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=1)
        choose = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(n, -1)
        _, experts = lax.top_k(choose, experts_per_token)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        weights = scale * weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights, experts.astype(jnp.int32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(probs, experts_per_token)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def _dense_experts(x, weights, experts, w_gate, w_up, w_down):
    """Every expert held on every token (``experts`` counts from the first
    one held; an expert elsewhere matches none). A token's weight for an
    expert it did
    not choose is zero; folded into the down projection's operand, the
    sum over experts is that projection's own contraction (over expert
    and width together), so no per-expert output is ever stored."""
    n_experts = w_gate.shape[0]
    combine = jnp.sum(
        weights[..., None] * (experts[..., None] == jnp.arange(n_experts)),
        axis=1)                                             # [N, E] f32
    gate = jnp.einsum("nd,edf->enf", x, w_gate,
                      preferred_element_type=jnp.float32)
    up = jnp.einsum("nd,edf->enf", x, w_up,
                    preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up * combine.T[..., None]).astype(x.dtype)
    return jnp.einsum("enf,efd->nd", hidden, w_down,
                      preferred_element_type=jnp.float32)


def _grouped_experts(x, weights, experts, live, w_gate, w_up, w_down):
    """(token, expert) pairs sorted by expert, three grouped matmuls, each
    token's k results gathered back and summed in top-k order (no
    scatter-add: a row's sum has one order whatever its neighbours are)."""
    n, k = experts.shape
    n_experts = w_gate.shape[0]
    # rows without a token, and pairs whose expert is held elsewhere, go to
    # a group past the last expert: sorted to the end, outside every
    # group, they meet no weights
    here = live[:, None] & (experts >= 0) & (experts < n_experts)
    pair_expert = jnp.where(here, experts, n_experts).reshape(-1)
    order = jnp.argsort(pair_expert, stable=True)           # [N*k]
    sizes = jnp.bincount(pair_expert, length=n_experts + 1)[
        :n_experts].astype(jnp.int32)
    xs = jnp.take(x, order // k, axis=0)                    # [N*k, D]
    gate = lax.ragged_dot(xs, w_gate, sizes,
                          preferred_element_type=jnp.float32)
    up = lax.ragged_dot(xs, w_up, sizes,
                        preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = lax.ragged_dot(hidden, w_down, sizes,
                         preferred_element_type=jnp.float32)
    # rows past the last group are not the grouped matmul's to define
    in_group = jnp.arange(n * k) < jnp.sum(sizes)
    out = jnp.where(in_group[:, None], out, 0.0)
    back = jnp.argsort(order)                               # pair -> row
    out = jnp.take(out, back, axis=0).reshape(n, k, -1)
    return jnp.sum(jnp.where(here, weights, 0.0)[..., None] * out, axis=1)


def routed_ffn(x, p: Dict[str, Any], *, experts_per_token: int,
               norm_topk_prob: bool = False,
               cast: Callable = lambda w: w,
               live=None, groups: Optional[Tuple[int, int, float]] = None,
               first: int = 0) -> Tuple[Any, Dict[str, Any]]:
    """The routed feed-forward on ``x [N, D]`` with the block's ``moe``
    parameters ``p`` (``init_experts``). ``cast`` brings an expert matrix
    to the compute dtype (the policy's ``cast_compute``); the router stays
    as stored. ``live [N]`` (bool, default all) marks the rows that hold a
    token: the others get ``y = 0``, count in no load and, in the sorted
    form, reach no expert. ``groups`` is ``route``'s; ``first`` is the
    router's index of the first expert held here (the stacked matrices say
    how many are).

    Returns ``(y [N, D] in x.dtype, info)`` with ``info["experts"]``
    ``[N, k]`` int32 (the router's indices), ``info["weights"]`` ``[N, k]``
    float32 and ``info["load"]`` ``[held]`` int32, the live (token, expert)
    pairs each expert held here received."""
    n = x.shape[0]
    held = p["w_gate"].shape[0]
    if live is None:
        live = jnp.ones((n,), bool)
    with jax.named_scope("moe.route"):
        weights, experts = route(x, p["router"], experts_per_token,
                                 norm_topk_prob, bias=p.get("bias"),
                                 groups=groups)
        weights = jnp.where(live[:, None], weights, 0.0)
        local = experts - first
        load = jnp.sum(
            live[:, None, None]
            & (local[..., None] == jnp.arange(held)),
            axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("moe.experts"):
        mats = (cast(p["w_gate"]), cast(p["w_up"]), cast(p["w_down"]))
        if n <= DENSE_MAX_TOKENS:
            y = _dense_experts(x, weights, local, *mats)
        else:
            y = _grouped_experts(x, weights, local, live, *mats)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            sh = p["shared"]
            hidden = (jax.nn.silu(x @ cast(sh["w_gate"]))
                      * (x @ cast(sh["w_up"])))
            y = y + jnp.where(live[:, None], hidden @ cast(sh["w_down"]),
                              0.0)
    return y.astype(x.dtype), {"experts": experts, "weights": weights,
                               "load": load}
