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

The experts take one of three forms, chosen from shapes at trace time
(``REACHED_MAX_ROWS``, ``DENSE_MAX_TOKENS``):

- up to 128 rows (every decode program, the short rungs of a prompt
  ladder), in a trace that takes no gradient, on a TPU, at widths the
  kernel has blocks for: only the experts that received a live pair are
  fetched (``pallas/reached_experts.py``: a work list made from ``load``,
  each listed expert's matrices read once as stored and rounded in VMEM).
  The step's time follows the experts its live rows reached, not the
  experts held nor the slots of the program: the rule reads the rows of
  the trace and leaves the skipping to the kernel, which is measured
  faster than or level with the dense form at every such row count (the
  table above ``REACHED_MAX_ROWS``).
- few tokens otherwise (a prompt of up to a thousand tokens; a short
  trace off the TPU, or one that takes a gradient): every
  expert runs on every token as one batched matmul and unchosen results
  get weight zero. At these counts the step waits for the expert weights,
  which both forms read, and the extra products hide under that read; no
  sort, no gather, no second copy of the weights.
- many tokens (a long prompt, a training batch): the (token, expert)
  pairs are sorted by expert, those whose expert is held here first, and
  the three projections are grouped matmuls (``jax.lax.ragged_dot``: each
  row meets only its expert's weights) over the pairs held here alone,
  ``_pass_rows`` sorted rows at a time: a pass gathers its rows' tokens,
  multiplies, and adds each result into its token's row. The size of a
  pass comes from the shapes (what an even router sends to the experts
  held, and a quarter more); how many passes run is what the block's
  routing needs, none where no token chose an expert here -- one loop, one
  program, no pair dropped. Pairs held elsewhere and rows that hold no
  token (``live`` false) sort behind the last group and reach no pass.
  Where one pass would take every pair anyway (every expert held here) or
  the trace takes a gradient, it is one pass with no loop, and each token
  gathers its k results back.

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
  through with weight 1 (``p["shared"]``), every chip alike; with
  ``p["shared"]["gate"]`` [D] its output is first multiplied by
  ``sigmoid(x . gate)``, one number a token (``qwen3_next``).

The share goes with either router: the softmax router of the head of this
page keeps its E outputs and its k a token just the same.

A row's sum over its experts has one order, fixed by the row's own choices
whatever the other rows of the block chose, and repeats bit for bit: the
dense form's contraction runs over the experts by index, the one-pass
sorted form adds a row's k results in the router's order (largest weight
first), and the passes add them by ascending expert index, as the dense
form does -- a pass's scatter-add applies its rows in sorted order onto a
row that starts at zero, one product at a time, so where the passes are
cut does not show (``chip_smoke.py`` holds the chip to it).

A prompt of more than ``2 ROW_BLOCK`` rows takes the sorted form
``ROW_BLOCK`` rows at a time: the sort, the loads and the passes' bound are
a block's, so a 28,672-token prompt runs the program of a 4,096-token
block seven times over (before the passes the form gathered one row of
``D`` numbers for every (token, expert) pair, held here or not: at ten
pairs a token that prompt's would have been 2.3 GB in float32 for one
layer's output alone; a pass's rows are a quarter of a block's pairs at
Qwen3-Next's share).

Scopes ``moe.route``, ``moe.experts`` and ``moe.shared`` name the parts in a
device trace.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.pallas import reached_experts as reached_kernel
from deeplearning4j_tpu.pallas.flash_attention import flash_default_interpret
from deeplearning4j_tpu.scopes import scope

__all__ = ["DENSE_MAX_TOKENS", "REACHED_MAX_ROWS", "init_experts", "route",
           "routed_ffn"]

# Up to this many tokens every expert runs on every token. Both forms
# read all the expert weights of a layer; the dense form's E/k-fold surplus
# of products hides under that read up to a few hundred rows, and the
# sorted form first writes a compute-dtype copy of the layer's experts for
# ``ragged_dot`` (the dense matmuls fuse the cast). On a v5e at OLMoE's
# widths with float32 storage (PERF.md section 6, PR 25): 32 rows 2.4 ms a
# layer dense against 5.3 sorted, 1,024 rows 4.9 against 7.6, 2,048 rows
# 9.9 against 9.7, where the dense form's intermediates are also 1.3 GiB.
DENSE_MAX_TOKENS = 1024

# Up to this many rows a trace that takes no gradient runs its experts in
# the reached form wherever the kernel has blocks for the widths
# (``reached_kernel.expert_blocks``): the rows of the trace decide, because
# the kernel skips by the live rows' ``load``, which no shape shows -- a
# decode program's rows are its slots, and a fifth to a half of them hold a
# request. On a v5e (PR 46; ``scripts/reached_form_bench.py``: float32
# storage, bf16 rows, router, load and work list included, four layers'
# own matrices in one program), ms a layer reached / dense, and the experts
# of 64 a layer reached:
#
#   rows live   OLMoE (D 2,048, F 1,024)     Mellum2 (D 2,304, F 896)
#    16   16    1.860 / 2.277  (55.3)        1.854 / 2.116  (56.0)
#    16    4    0.904 / 2.277  (26.5)        0.874 / 2.116  (26.0)
#    32   32    2.128 / 2.277  (63.3)        2.063 / 2.115  (62.3)
#    32   16    1.952 / 2.277  (58.0)        1.825 / 2.117  (55.0)
#    64   64    2.155 / 2.277  (64)          2.121 / 2.121  (64)
#    64   12    1.630 / 2.277  (48.3)        1.677 / 2.118  (50.5)
#   128  128    2.153 / 2.279  (64)          2.122 / 2.118  (64)
#   128   32    2.129 / 2.276  (63.3)        2.079 / 2.120  (62.8)
#   256  256    2.185 / 2.244  (64)          2.175 / 2.131  (64)
#   256   64    2.183 / 2.245  (64)          2.176 / 2.132  (64)
#
# The kernel streams what it fetches at 745-748 GB/s whatever the rows up
# to 128 (729-737 at 256, where its products on 256-row tiles begin to
# show); XLA's pass over every expert runs at 707 GB/s at OLMoE's widths
# and 748 at Mellum2's. So up to 128 rows the kernel is ahead by the experts
# it skips (28 % at 12 live rows of 64, 60 % at 4 of 16) plus 0-5.5 % of
# stream, and level (+0.2 %, the runs' own scatter) where every expert is
# reached at Mellum2's widths; at 256 rows it is 2 % behind there in both
# columns. 128 is the last row count of the table at which it loses nowhere.
# With even routing a share ``1 - exp(-n k / E)`` of the held experts is
# reached by ``n`` live rows; a trained router is more skewed and reaches
# fewer. At Ling's decode step (64 rows, 8 of 512, 64 held: 18-22 reached a
# layer) a layer takes 0.72 ms where the dense form took 2.05 (PR 30).
REACHED_MAX_ROWS = 128

# Rows the sorted form takes at a time of a prompt longer than twice this
# (the module's docstring); the ladders' long rungs are multiples of it.
ROW_BLOCK = 4096

# A pass of the sorted form (``_pass_rows``): what an even router sends to
# the experts held here and a quarter more, in whole tiles of the grouped
# matmul's rows. At Qwen3-Next's block (4,096 rows x 10, 128 of 512 held)
# that is 12,800 rows of 40,960.
PASS_MARGIN = 1.25
PASS_TILE = 128


def init_experts(key, d_model: int, d_ff: int, num_experts: int, dtype, *,
                 held: Optional[int] = None, bias: bool = False,
                 shared_width: int = 0, shared_gate: bool = False):
    """Glorot-normal router and stacked expert matrices: ``router``
    [D, E], ``w_gate`` / ``w_up`` [held, D, F], ``w_down`` [held, F, D]
    (``held`` defaults to E: every expert is here). ``bias``: an expert
    bias ``bias`` [E] for choosing (normal x 0.01: small, and not zero, so
    that a seeded model exercises it). ``shared_width`` > 0: a shared
    expert ``shared.{w_gate, w_up, w_down}`` of that width, and with
    ``shared_gate`` its gate vector ``shared.gate`` [D]."""
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
        if shared_gate:
            p["shared"]["gate"] = glorot(
                jax.random.fold_in(ks[0], 1), (d,), d, 1)
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


def swiglu(gate, up, limit: Optional[float] = None):
    """``silu(gate) * up``; with ``limit`` (GLM-5.3's ``swiglu_limit``, as
    Ling reads its limit lists) the gate is first clamped to at most
    ``limit`` and the up projection to ``[-limit, limit]``. ``None`` is the
    plain product, op for op what the forms below had inline."""
    if limit is not None:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def _dense_experts(x, weights, experts, w_gate, w_up, w_down, limit=None):
    """Every expert held on every token (``experts`` counts from the first
    one held; an expert elsewhere matches none). A token's weight for an
    expert it did
    not choose is zero; folded into the down projection's operand, the
    sum over experts is that projection's own contraction (over expert
    and width together), so no per-expert output is ever stored."""
    combine = _combine(weights, experts, w_gate.shape[0])   # [N, E] f32
    gate = jnp.einsum("nd,edf->enf", x, w_gate,
                      preferred_element_type=jnp.float32)
    up = jnp.einsum("nd,edf->enf", x, w_up,
                    preferred_element_type=jnp.float32)
    hidden = (swiglu(gate, up, limit) * combine.T[..., None]).astype(x.dtype)
    return jnp.einsum("enf,efd->nd", hidden, w_down,
                      preferred_element_type=jnp.float32)


def _kernel_backend() -> Optional[str]:
    """How the reached form's Pallas kernel may run here: ``"mosaic"``
    where a TPU is attached, ``None`` elsewhere (the dense form stays;
    tests put ``"interpret"`` here)."""
    return None if flash_default_interpret() else "mosaic"


def _reached_blocks(x, w_gate, train: bool):
    """The reached form's blocks for this trace of ``x [N, D]`` against
    ``w_gate [held, D, F]`` as stored, or ``None`` where another form is
    taken."""
    n = x.shape[0]
    if train or n > REACHED_MAX_ROWS or _kernel_backend() is None:
        return None
    return reached_kernel.expert_blocks(n, x.shape[1], w_gate.shape[2],
                                        w_gate.dtype)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret", "limit"))
def _reached(x, combine, load, w_gate, w_up, w_down, *, blocks, interpret,
             limit=None):
    """``reached_kernel.reached_experts`` under a ``jit`` of its own, as
    ``_grouped_experts`` has one: the layers of a program share one trace
    and one lowering of the kernel (a call site of its own costs 0.1 s of
    every process's warm start, a program 0.4: at four layers and five
    programs OLMoE's would have been 2 s on a 21 s set-up). The scope is
    opened again inside: a Mosaic call is named in a trace by the innermost
    entry of its name stack, which would be this function's name."""
    with scope("moe.experts"):
        return reached_kernel.reached_experts(
            x, combine, load, w_gate, w_up, w_down, blocks=blocks,
            interpret=interpret, limit=limit)


def _combine(weights, experts, held: int):
    """``[N, held]`` float32: a row's weight for each expert held
    (``experts`` counts from the first one held; an expert elsewhere
    matches none), zero for an expert it did not choose."""
    return jnp.sum(
        weights[..., None] * (experts[..., None] == jnp.arange(held)),
        axis=1)


def _load(live, experts, held: int):
    """``[held]`` int32: the pairs of live rows each expert held received
    (``experts [N, k]`` counts from the first one held)."""
    return jnp.sum(
        live[:, None, None] & (experts[..., None] == jnp.arange(held)),
        axis=(0, 1), dtype=jnp.int32)


def _pass_rows(n: int, k: int, held: int, num_experts: int,
               train: bool) -> int:
    """Rows one pass of the sorted form takes of a block of ``n`` rows with
    ``k`` pairs each, where ``held`` of the router's ``num_experts`` are
    here: what an even router sends here, ``n k held / num_experts``, and
    ``PASS_MARGIN`` more, in whole ``PASS_TILE``s -- or all ``n k`` pairs in
    one pass where that is no fewer (every expert held, or nearly) and where
    the trace takes a gradient (the passes are a ``while``, which has no
    reverse mode). From shapes alone: a block has one program whatever its
    tokens choose, and the router's skew shows in the number of passes."""
    pairs = n * k
    if train:
        return pairs
    tiles = math.ceil(PASS_MARGIN * pairs * held / num_experts / PASS_TILE)
    return min(tiles * PASS_TILE, pairs)


def _expert_rows(xs, sizes, w_gate, w_up, w_down, limit=None):
    """The three grouped matmuls on rows sorted by expert, ``sizes [held]``
    of them each; float32. Rows past the last group are not the grouped
    matmul's to define: zero."""
    gate = lax.ragged_dot(xs, w_gate, sizes,
                          preferred_element_type=jnp.float32)
    up = lax.ragged_dot(xs, w_up, sizes,
                        preferred_element_type=jnp.float32)
    hidden = swiglu(gate, up, limit).astype(xs.dtype)
    out = lax.ragged_dot(hidden, w_down, sizes,
                         preferred_element_type=jnp.float32)
    in_group = jnp.arange(xs.shape[0]) < jnp.sum(sizes)
    return jnp.where(in_group[:, None], out, 0.0)


@functools.partial(jax.jit, static_argnames=("pass_rows", "limit"))
def _grouped_experts(x, weights, experts, live, w_gate, w_up, w_down, *,
                     pass_rows: int, limit=None):
    """(token, expert) pairs sorted by expert, the pairs whose expert is
    held here first; those rows alone are gathered, meet the three grouped
    matmuls and go back to their tokens, ``pass_rows`` sorted rows at a
    time, in as many passes as the pairs held here need: ``(y [N, D]
    float32, rows run int32)``. No pair is dropped and no pass is run for
    pairs held elsewhere; no token choosing an expert here is no pass.

    A pass adds its rows into ``y`` (a scatter-add, applied in the order of
    the sorted rows), so a row's sum runs over its experts in ascending
    order, as the dense form's contraction does, from zero, one product at
    a time: it does not depend on where the passes were cut, hence not on
    what the other rows chose, and repeats bit for bit.

    ``pass_rows == N k`` (``_pass_rows``: every expert held, a trace that
    takes a gradient) is one pass over every pair with no loop, and each
    token gathers its k results back and sums them in top-k order.

    A ``jit`` of its own: the layers of a program, and the rungs of a
    ladder that share a block's shapes, trace and lower it once (a serving
    process's warm start traces and lowers every program it loads)."""
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
    weights = jnp.where(here, weights, 0.0)
    if pass_rows == n * k:
        out = _expert_rows(jnp.take(x, order // k, axis=0), sizes,
                           w_gate, w_up, w_down, limit)
        back = jnp.argsort(order)                           # pair -> row
        out = jnp.take(out, back, axis=0).reshape(n, k, -1)
        return (jnp.sum(weights[..., None] * out, axis=1),
                jnp.int32(pass_rows))
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    passes = (ends[-1] + pass_rows - 1) // pass_rows
    # the last pass may reach past the pairs: those rows lie in no group
    order = jnp.pad(order, (0, -(n * k) % pass_rows))
    pair_weight = weights.reshape(-1)

    def one_pass(p, y):
        lo = p * pass_rows
        pairs = lax.dynamic_slice(order, (lo,), (pass_rows,))
        rows = pairs // k
        # this pass's share of each group
        share = jnp.clip(jnp.minimum(ends, lo + pass_rows)
                         - jnp.maximum(starts, lo), 0)
        out = _expert_rows(jnp.take(x, rows, axis=0), share,
                           w_gate, w_up, w_down, limit)
        return y.at[rows].add(jnp.take(pair_weight, pairs)[:, None] * out)

    y = lax.fori_loop(0, passes, one_pass,
                      jnp.zeros((n, w_down.shape[2]), jnp.float32))
    return y, passes * pass_rows


def routed_ffn(x, p: Dict[str, Any], *, experts_per_token: int,
               norm_topk_prob: bool = False,
               cast: Callable = lambda w: w,
               live=None, groups: Optional[Tuple[int, int, float]] = None,
               first: int = 0, train: bool = False,
               limit: Optional[float] = None
               ) -> Tuple[Any, Dict[str, Any]]:
    """The routed feed-forward on ``x [N, D]`` with the block's ``moe``
    parameters ``p`` (``init_experts``). ``cast`` brings an expert matrix
    to the compute dtype (the policy's ``cast_compute``); the router stays
    as stored. ``live [N]`` (bool, default all) marks the rows that hold a
    token: the others get ``y = 0``, count in no load and, in the sorted
    form, reach no expert. ``groups`` is ``route``'s; ``first`` is the
    router's index of the first expert held here (the stacked matrices say
    how many are). ``train``: the trace takes a gradient, which the
    reached form does not have. ``limit``: ``swiglu``'s clamp, on every
    routed expert and on the shared one.

    Returns ``(y [N, D] in x.dtype, info)`` with ``info["experts"]``
    ``[N, k]`` int32 (the router's indices), ``info["weights"]`` ``[N, k]``
    float32, ``info["load"]`` ``[held]`` int32, the live (token, expert)
    pairs each expert held here received, and ``info["read"]`` int32, the
    held experts whose matrices this trace fetches: those with ``load >
    0`` in the reached form, all of them in the other two, and
    ``info["run"]`` int32, the sorted rows the passes of the sorted form
    took through the grouped matmuls (``_grouped_experts``; over
    ``load.sum()`` it is the form's waste, 1 at best), 0 in the other
    two."""
    n = x.shape[0]
    held = p["w_gate"].shape[0]
    if live is None:
        live = jnp.ones((n,), bool)
    with scope("moe.route"):
        weights, experts = route(x, p["router"], experts_per_token,
                                 norm_topk_prob, bias=p.get("bias"),
                                 groups=groups)
        weights = jnp.where(live[:, None], weights, 0.0)
        local = experts - first
        load = _load(live, local, held)
    with scope("moe.experts"):
        stored = (p["w_gate"], p["w_up"], p["w_down"])
        blocks = _reached_blocks(x, stored[0], train)
        read, run = jnp.int32(held), jnp.int32(0)
        if blocks is not None:
            # the kernel rounds each fetched block as ``cast`` would
            rounded = jax.eval_shape(cast, stored[0]).dtype
            y = _reached(
                x.astype(jnp.promote_types(x.dtype, rounded)),
                _combine(weights, local, held), load, *stored,
                blocks=blocks, interpret=_kernel_backend() == "interpret",
                limit=limit)
            read = jnp.sum(load > 0, dtype=jnp.int32)
        elif n <= DENSE_MAX_TOKENS:
            y = _dense_experts(x, weights, local, *map(cast, stored),
                               limit=limit)
        else:
            blocked = n > 2 * ROW_BLOCK and n % ROW_BLOCK == 0
            sort = functools.partial(
                _grouped_experts, pass_rows=_pass_rows(
                    ROW_BLOCK if blocked else n, experts_per_token, held,
                    p["router"].shape[1], train), limit=limit)
            matrices = tuple(map(cast, stored))
            if blocked:
                y, run = lax.map(
                    lambda rows: sort(*rows, *matrices),
                    tuple(a.reshape((-1, ROW_BLOCK) + a.shape[1:])
                          for a in (x, weights, local, live)))
                y, run = y.reshape(n, -1), jnp.sum(run)
            else:
                y, run = sort(x, weights, local, live, *matrices)
    if "shared" in p:
        with scope("moe.shared"):
            sh = p["shared"]
            if limit is None:
                hidden = (jax.nn.silu(x @ cast(sh["w_gate"]))
                          * (x @ cast(sh["w_up"])))
            else:
                hidden = swiglu(x @ cast(sh["w_gate"]), x @ cast(sh["w_up"]),
                                limit)
            keep = live[:, None]
            out = hidden @ cast(sh["w_down"])
            if "gate" in sh:
                out = out * jax.nn.sigmoid(jnp.dot(
                    x.astype(jnp.float32), sh["gate"].astype(jnp.float32),
                    precision=lax.Precision.HIGHEST))[:, None].astype(
                        out.dtype)
            y = y + jnp.where(keep, out, 0.0)
    return y.astype(x.dtype), {"experts": experts, "weights": weights,
                               "load": load, "read": read, "run": run}
