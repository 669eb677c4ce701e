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

Scopes ``moe.route`` and ``moe.experts`` name the two halves in a device
trace.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

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


def init_experts(key, d_model: int, d_ff: int, num_experts: int, dtype):
    """Glorot-normal router and stacked expert matrices: ``router``
    [D, E], ``w_gate`` / ``w_up`` [E, D, F], ``w_down`` [E, F, D]."""
    kr, kg, ku, kd = jax.random.split(key, 4)

    def glorot(k, shape, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dtype)
        return jax.random.normal(k, shape, dtype) * scale

    e, d, f = num_experts, d_model, d_ff
    return {"router": glorot(kr, (d, e), d, e),
            "w_gate": glorot(kg, (e, d, f), d, f),
            "w_up": glorot(ku, (e, d, f), d, f),
            "w_down": glorot(kd, (e, f, d), f, d)}


def route(x, router, experts_per_token: int, norm_topk_prob: bool = False):
    """``x [N, D]``, ``router [D, E]`` -> ``(weights [N, k] float32,
    experts [N, k] int32)``: the k largest of the float32 softmax over
    all E experts, largest first (ties to the lower index)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(probs, experts_per_token)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def _dense_experts(x, weights, experts, w_gate, w_up, w_down):
    """Every expert on every token. A token's weight for an expert it did
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
    # rows without a token go to a group past the last expert: sorted to
    # the end, outside every group, they meet no weights
    pair_expert = jnp.where(live[:, None], experts, n_experts).reshape(-1)
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
    return jnp.sum(weights[..., None] * out, axis=1)


def routed_ffn(x, p: Dict[str, Any], *, experts_per_token: int,
               norm_topk_prob: bool = False,
               cast: Callable = lambda w: w,
               live=None) -> Tuple[Any, Dict[str, Any]]:
    """The routed feed-forward on ``x [N, D]`` with the block's ``moe``
    parameters ``p`` (``init_experts``). ``cast`` brings an expert matrix
    to the compute dtype (the policy's ``cast_compute``); the router stays
    as stored. ``live [N]`` (bool, default all) marks the rows that hold a
    token: the others get ``y = 0``, count in no load and, in the sorted
    form, reach no expert.

    Returns ``(y [N, D] in x.dtype, info)`` with ``info["experts"]``
    ``[N, k]`` int32, ``info["weights"]`` ``[N, k]`` float32 and
    ``info["load"]`` ``[E]`` int32, the live (token, expert) pairs each
    expert received."""
    n = x.shape[0]
    n_experts = p["router"].shape[1]
    if live is None:
        live = jnp.ones((n,), bool)
    with jax.named_scope("moe.route"):
        weights, experts = route(x, p["router"], experts_per_token,
                                 norm_topk_prob)
        weights = jnp.where(live[:, None], weights, 0.0)
        load = jnp.sum(
            live[:, None, None]
            & (experts[..., None] == jnp.arange(n_experts)),
            axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("moe.experts"):
        mats = (cast(p["w_gate"]), cast(p["w_up"]), cast(p["w_down"]))
        if n <= DENSE_MAX_TOKENS:
            y = _dense_experts(x, weights, experts, *mats)
        else:
            y = _grouped_experts(x, weights, experts, live, *mats)
    return y.astype(x.dtype), {"experts": experts, "weights": weights,
                               "load": load}
