"""Learned sparse attention over latent rows (DeepSeek sparse attention, the
lightning indexer of DeepSeek-V3.2-Exp, as GLM-5.2's ``glm_moe_dsa`` layers
use it): a small indexer scores every cached position for a query, the
``topk`` best are selected, and the latent-attention mixer (``models/mla.py``)
attends those rows and no others.

    q^I_t   = c_q W^I_q                     (hI x dI)   c_q: the compressed query
    k^I_s   = layernorm(x_s W^I_k)          (dI)        one key for all hI heads
    q^I, k^I: RoPE on the first ``rope_dim`` of the dI dimensions
    w_t     = x_t W^I_w * hI^-1/2 * dI^-1/2 (hI)
    I(t, s) = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)
    S_t     = the topk positions s <= t of largest I(t, s); all while t < topk

A layer's indexer is ``"full"`` (it has the parameters above, computes
``S_t`` and hands it on) or ``"shared"`` (no indexer parameters: it attends
the ``S_t`` of the nearest ``"full"`` layer before it). What a position
leaves behind in a ``"full"`` layer, beside its latent row, is its index key
``k^I_s`` (``serving/kv_cache.py``'s ``index`` rows).

The selection is **exact** (ties to the lower position): an approximate
top-k below recall 1 would be another model. It has two forms, chosen from
the shapes at trace time, and the attention over ``S_t`` (the absorbed form of
``mla.attend_latent``) follows the form it is handed:

- **a mask** ``[b, q, T]``, from the k-th largest score found by bisection on
  the scores' bits (32 passes of compare-and-count, no sort): the queries
  attend all T keys under it as dense products. Several queries a row: a
  prefill block, where 18 MFLOP a key and 128 queries on the MXU are cheaper
  than a gather of 128 x 2,048 rows of 1,280 B (XLA:TPU moves 11 ns a row;
  PERF.md section 6, PR 31).
- **positions** ``(idx [b, 1, k], valid)``, ascending: the same mask, its set
  bits packed into k positions (``mask_positions``: counts in tiles of 128
  lanes, a prefix over the tiles, a one-hot product that fetches each
  position's tile; no sort, no gather, no scatter). The query gathers its k
  rows and attends them, k rows whatever the context holds. One query a row:
  a decode step. Threshold and packing take 0.16 ms for 16 x 32,768 scores
  (a top-k primitive at k = 2,048 is a sort of the whole row on a TPU: 2.9 ms;
  PERF.md section 6, PR 32). Its two reads of a cache follow a **work list
  of the live rows** (``live_slots``): a loop takes one row a trip, scores
  its index keys or gathers its k latent rows, and has as many trips as rows
  owe a token, so a slot that holds no request costs neither 8 MB of index
  keys nor 2,048 rows at 11 ns each (PERF.md section 6, PR 34). Threshold,
  packing and the attention over the gathered rows stay one op over all rows.

**Pooled index keys** (``dsa["pool"]`` = n > 1; GLM-5.3's ``index_kpool``,
read literally, ``docs/glm53_flash.md``): pool p holds positions n p .. n p +
n - 1 and its key is the mean of their index keys (after norm and RoPE). The
index cache keeps that mean alone for a complete pool and, beside it, the
running sum of the open one (``pool_keys``; ``serving/kv_cache.py``): 1 / n
of the rows. A query at t scores the P(t) = floor(t / n) pools that end
before its own, selects the ``topk / n`` best (all while P(t) <= topk / n),
attends their n positions each, and always the **tail**, positions n P(t) ..
t, its own open pool up to itself, never scored:

    I(t, p) = sum_j w_{t,j} relu(q^I_{t,j} . mean_{s in pool p} k^I_s)
    S_t     = union of the topk / n pools p < P(t) of largest I(t, p),
              and {n P(t), ..., t}

Both forms carry it: the mask is the pools' mask repeated n times a pool
with the tail set, the positions are ``topk`` pool positions and then the n
of the tail (``valid`` false beyond t). Scope ``dsa.pool`` names the pooling
itself.

Queries are taken ``ATTEND_BLOCK`` at a time, fewer where a block's float32
scores ``[q, hI, T]`` or logits ``[H, q, T]`` would pass ``ATTEND_BYTES``.
Scopes ``dsa.index`` (projections, scores, selection) and ``mla.attend``
(gather and attention).
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.models import mla
from deeplearning4j_tpu.scopes import scope

__all__ = ["ATTEND_BLOCK", "init_indexer", "index_project",
           "index_scores", "live_slots", "select", "kth_largest_mask",
           "mask_positions", "selected_positions", "attend_selected",
           "pool_keys", "selection_width"]

ATTEND_BLOCK = mla.QUERY_BLOCK     # queries alive at once


def init_indexer(key, d_model: int, q_rank: int, dims: Dict[str, int],
                 dtype) -> Dict[str, Any]:
    """Glorot-normal ``wq`` [rq, hI dI], ``wk`` [D, dI], ``ww`` [D, hI];
    ``k_norm.{g, b}`` [dI] (a LayerNorm)."""
    ks = jax.random.split(key, 3)
    h, d = dims["n_heads"], dims["head_dim"]

    def glorot(k, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dtype)
        return jax.random.normal(k, (fan_in, fan_out), dtype) * scale

    return {"wq": glorot(ks[0], q_rank, h * d),
            "wk": glorot(ks[1], d_model, d),
            "k_norm": {"g": jnp.ones((d,), dtype),
                       "b": jnp.zeros((d,), dtype)},
            "ww": glorot(ks[2], d_model, h)}


def index_project(x, c_q, p, *, dims: Dict[str, int], rope, layernorm,
                  cast: Callable = lambda w: w):
    """``x`` [b, t, D], ``c_q`` [b, t, rq] -> ``(q^I [b, t, hI, dI], k^I
    [b, t, dI], w [b, t, hI] float32)``, queries and key after RoPE on
    their first ``rope_dim`` dimensions. ``rope(a [b, t, h, rope_dim])`` and
    ``layernorm(a, g, b)`` are the model's."""
    b, t, _ = x.shape
    h, d, rd = dims["n_heads"], dims["head_dim"], dims["rope_dim"]
    with scope("dsa.index"):
        q = (c_q @ cast(p["wq"])).reshape(b, t, h, d)
        q = jnp.concatenate([rope(q[..., :rd]), q[..., rd:]], axis=-1)
        k = layernorm(x @ cast(p["wk"]), p["k_norm"]["g"], p["k_norm"]["b"])
        k = jnp.concatenate(
            [rope(k[:, :, None, :rd])[:, :, 0], k[..., rd:]], axis=-1)
        w = (x @ cast(p["ww"])).astype(jnp.float32) * (h ** -0.5 * d ** -0.5)
    return q, k, w


def pool_keys(keys, pool: int):
    """``keys`` [b, t, dI] -> [b, ceil(t / pool), dI] in ``keys``' dtype: the
    mean of each run of ``pool`` positions, taken in float32 (a last run
    short of ``pool`` is padded with zeros: an open pool, which no query
    scores)."""
    b, t, d = keys.shape
    with scope("dsa.pool"):
        k = jnp.pad(keys.astype(jnp.float32), ((0, 0), (0, -t % pool), (0, 0)))
        return jnp.mean(k.reshape(b, -1, pool, d), axis=2).astype(keys.dtype)


def selection_width(dims: Dict[str, int], rows: int) -> int:
    """Positions a query's selection names at most, over a cache of ``rows``
    positions: ``min(topk, rows)``, and with pooled keys the whole pools
    among them and the tail's ``pool`` more."""
    pool = int(dims.get("pool", 1))
    if pool == 1:
        return min(int(dims["topk"]), rows)
    return (min(int(dims["topk"]) // pool, rows // pool) + 1) * pool


# the float32 logits [heads, block, keys] of one block of queries may take
# this much: ``ATTEND_BLOCK`` halves until they do (64 heads x 128 queries x
# 32,768 keys is the gigabyte exactly; against 57,344 keys 128 queries would
# take 1.9 GB beside 8 GB of weights and the pool, so 64 are taken)
ATTEND_BYTES = 1 << 30


def _by_query_blocks(fn, *args, heads: int = 0, keys: int = 0):
    """``mla.by_query_blocks``, ``ATTEND_BLOCK`` queries at a time, fewer
    where ``fn`` makes float32 logits [heads, block, keys] of more than
    ``ATTEND_BYTES``."""
    block = ATTEND_BLOCK
    while block > 8 and 4 * heads * block * keys > ATTEND_BYTES:
        block //= 2
    return mla.by_query_blocks(fn, *args, block=block)


def live_slots(live, slots: int):
    """The work list of the one-query form's reads: ``(order [slots] int32,
    count)``, the rows that owe a token (``live`` [slots] bool; None: all of
    them) first, in ascending order, and how many they are; ``order`` from
    ``count`` on is ``slots``, no row. Plain arithmetic over numpy or jax
    values: ``select`` and ``attend_selected`` loop over it, and the
    server's host counter (``rows_gathered``) counts with it."""
    if live is None:
        live = np.ones(slots, bool)
    xp = jnp if isinstance(live, jax.Array) else np
    ends = xp.cumsum(xp.asarray(live, dtype=xp.int32))
    order = xp.sum(xp.arange(slots)[:, None] >= ends[None, :], axis=1,
                   dtype=xp.int32)
    return order, ends[-1]


def index_scores(iq, iw, keys):
    """``I(t, s)`` [b, q, T] float32 of queries ``iq`` [b, q, hI, dI] with
    head weights ``iw`` [b, q, hI] against index keys ``keys`` [b, T, dI]."""
    s = jnp.einsum("bqhd,btd->bqht", iq.astype(keys.dtype), keys,
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("bqht,bqh->bqt", jax.nn.relu(s), iw)
    # one zero: a shut relu times a negative weight is -0.0, which a sort's
    # total order and the bits put below +0.0
    return jnp.where(s == 0, 0.0, s)


def kth_largest_mask(scores, k: int, unroll=None):
    """``[..., T]`` bool: the k largest of each row of ``scores`` (float32;
    -inf marks what may not be chosen and is never set), equal scores to the
    lower index; a row with fewer than k finite scores has them all. No
    sort: the k-th largest value is found one bit at a time, 32 counts of
    the row against a candidate, on the scores' bits in an order-preserving
    unsigned form. ``unroll``: ``lax.fori_loop``'s (a decode step's one
    query a row has the 32 counts in a line: 0.06 ms less for 16 x 32,768
    scores than 32 trips of a loop on a TPU)."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    keys = jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31))
    keys = lax.bitcast_convert_type(keys, jnp.uint32)   # larger = larger score

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, kth)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32),
                        unroll=unroll)
    above = keys > kth[..., None]
    ties = (keys == kth[..., None])
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    ties &= jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room
    return (above | ties) & (scores > -jnp.inf)


LANES = 128     # a tile of ``mask_positions``: the lanes of a vector register


def mask_positions(mask, k: int):
    """``(idx [..., k] int32, valid [..., k] bool)``: the positions of the
    set bits of each row of ``mask`` [..., T] in ascending order, the first
    k of them; ``valid`` is false from the row's count on, and ``idx`` there
    is some position below T. No sort, no gather, no scatter, no loop over
    the keys: the row in tiles of 128 lanes, the set bits counted along each
    tile (a product with a triangle of ones: counts to 128 are exact in
    bfloat16) and over the tiles before it; output r lies in the tile whose
    running total first passes r (a compare against every tile's), that
    tile's running counts come by a one-hot product, and its lane is where
    they first pass what r has left."""
    t = mask.shape[-1]
    n = -(-t // LANES)
    tiles = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, n * LANES - t)])
    tiles = tiles.reshape(mask.shape[:-1] + (n, LANES)).astype(jnp.bfloat16)
    running = jnp.einsum(
        "...nl,lm->...nm", tiles,
        jnp.triu(jnp.ones((LANES, LANES), tiles.dtype)),
        preferred_element_type=jnp.float32).astype(tiles.dtype)
    count = running[..., -1].astype(jnp.int32)                # [..., n]
    upto = jnp.cumsum(count, axis=-1)           # set bits to a tile's end
    r = jnp.arange(k, dtype=jnp.int32)
    passed = upto[..., None, :] <= r[:, None]                 # [..., k, n]
    tile = jnp.sum(passed, axis=-1, dtype=jnp.int32)
    left = r - jnp.sum(jnp.where(passed, count[..., None, :], 0), axis=-1)
    mine = jnp.einsum(
        "...kn,...nl->...kl", (tile[..., None] == jnp.arange(n)).astype(
            running.dtype), running, preferred_element_type=jnp.float32)
    lane = jnp.sum(mine <= left[..., None].astype(jnp.float32), axis=-1,
                   dtype=jnp.int32)
    return (jnp.minimum(tile * LANES + lane, t - 1), r < upto[..., -1:])


def select(iq, iw, keys, q_pos, topk: int, pool: int = 1):
    """The selection ``S_t`` of each query, the positions ``s <=
    q_pos[b, q]`` of ``keys`` [b, T, dI] with the k = min(topk, T) largest
    index scores, in one of two forms (the module's docstring). With
    ``pool`` > 1 ``keys`` are pooled keys [b, T / pool, dI] and the
    selection is over positions all the same: the ``topk / pool`` best
    pools that end before the query's own, ``pool`` positions each, and
    the tail (``_select_pooled``).

    - ``mask [b, q, T]`` bool. Several queries a row.
    - ``(idx [b, q, k] int32, valid [b, q, k] bool)``, the mask's positions
      in ascending order; a query with fewer than k positions behind it
      selects them all, and ``valid`` is false on the rest of its row
      (whose ``idx`` are then positions it may not see). One query a row.
      A row whose query stands before every key (``q_pos < 0``: a slot that
      owes no token, ``serving/engine._index_selection``) has nothing to
      select and its keys are not read: the scores are taken a row a trip
      of a loop over the others (``live_slots``)."""
    if pool > 1:
        return _select_pooled(iq, iw, keys, q_pos, int(topk) // pool, pool)
    b, t = keys.shape[:2]
    k = min(int(topk), t)

    def behind(scores, q_pos):
        return jnp.where(jnp.arange(t) <= q_pos[..., None], scores, -jnp.inf)

    def block(iq, iw, q_pos):
        return kth_largest_mask(behind(index_scores(iq, iw, keys), q_pos), k)

    def one(iq, iw, q_pos):
        scores = _scores_of_live(iq, iw, keys, q_pos)
        return mask_positions(
            kth_largest_mask(behind(scores, q_pos), k, unroll=True), k)

    with scope("dsa.index"):
        if iq.shape[1] == 1:
            return one(iq, iw, q_pos)
        return _by_query_blocks(block, iq, iw, q_pos, heads=iq.shape[2],
                                keys=t)


def _scores_of_live(iq, iw, keys, q_pos):
    """``index_scores`` [b, 1, T] of one query a row, a row a trip of a loop
    over the rows whose query stands at a position (``q_pos`` [b, 1] >= 0:
    ``live_slots``); the others' keys are not read and their scores are
    -inf."""
    b, t = keys.shape[:2]
    order, count = live_slots(q_pos[:, 0] >= 0, b)

    def trip(i, scores):
        s = order[i]
        return lax.dynamic_update_slice_in_dim(scores, index_scores(*(
            lax.dynamic_slice_in_dim(a, s, 1) for a in (iq, iw, keys))),
            s, 0)

    return lax.fori_loop(0, count, trip,
                         jnp.full((b, 1, t), -jnp.inf, jnp.float32))


def _select_pooled(iq, iw, keys, q_pos, k_pools: int, pool: int):
    """``select`` over pooled keys ``keys`` [b, Tp, dI]: query (b, q) at
    position t = ``q_pos`` scores the pools p < P(t) = t // pool, keeps the
    ``min(k_pools, Tp)`` best and its tail ``pool P(t) .. t``. A mask [b, q,
    Tp pool] over positions, or (one query a row) ``(idx [b, 1, (k + 1)
    pool], valid)``: the pools' positions ascending, then the tail's. A row
    with ``q_pos < 0`` (a slot that owes no token) reads no key and selects
    nothing."""
    b, tp = keys.shape[:2]
    k = min(k_pools, tp)
    inside = jnp.arange(pool)

    def before(scores, q_pos):      # pools that end before the query's own
        return jnp.where(jnp.arange(tp) < (q_pos // pool)[..., None],
                         scores, -jnp.inf)

    def block(iq, iw, q_pos):
        pools = kth_largest_mask(before(index_scores(iq, iw, keys), q_pos), k)
        at = jnp.arange(tp * pool)
        tail = ((at >= (q_pos // pool * pool)[..., None])
                & (at <= q_pos[..., None]))
        return jnp.repeat(pools, pool, axis=-1) | tail

    def one(iq, iw, q_pos):
        scores = _scores_of_live(iq, iw, keys, q_pos)
        idx, valid = mask_positions(
            kth_largest_mask(before(scores, q_pos), k, unroll=True), k)
        idx = (idx[..., None] * pool + inside).reshape(b, 1, k * pool)
        valid = jnp.repeat(valid, pool, axis=-1)
        # below the cache's rows whatever the cursor: a gather's index
        tail = jnp.minimum(jnp.maximum(q_pos, 0)[..., None] // pool * pool
                           + inside, tp * pool - 1)
        return (jnp.concatenate([idx, tail], axis=-1),
                jnp.concatenate([valid, (tail <= q_pos[..., None])], axis=-1))

    with scope("dsa.index"):
        if iq.shape[1] == 1:
            return one(iq, iw, q_pos)
        return _by_query_blocks(block, iq, iw, q_pos, heads=iq.shape[2],
                                keys=tp)


def selected_positions(selection, k: int):
    """A selection of either form as positions ``[b, q, k]`` int32 in
    ascending order, -1 where a query selected fewer than k. For a record of
    it: a mask is packed as ``select`` packs one (``mask_positions``)."""
    if not isinstance(selection, tuple):
        selection = mask_positions(selection, k)
    idx, valid = selection
    return jnp.where(valid, idx, -1)


def attend_selected(q_nope, q_rope, rows, selection, p, *, dims,
                    cast: Callable = lambda w: w, live=None):
    """Latent attention of each query over its own selected rows:
    ``q_nope`` [b, q, H, dn], ``q_rope`` [b, q, H, dr], ``rows`` [b, T, >=
    r + dr] (cached latent rows), ``selection`` = ``select``'s, in either
    form. Returns ``o`` [b, q, H, dv]. From positions: ``mla.attend_latent``
    with every query a batch row of its own, its keys the k rows gathered
    for it — gathered from ``rows`` itself a row of the batch a trip, for
    the rows that owe a token (``live`` [b] bool; None: all; ``live_slots``);
    the others attend zeros and no row of theirs is fetched. From a mask:
    ``mla.attend_latent`` over all T rows under it."""
    b = rows.shape[0]

    def gathered(q_nope, q_rope, idx, valid):
        n, k = idx.shape[1], idx.shape[2]
        order, count = live_slots(live, b)

        def trip(i, picked):
            s = jnp.asarray(order)[i]
            at = lax.dynamic_index_in_dim(idx, s, keepdims=False)   # [n, k]
            return lax.dynamic_update_slice_in_dim(
                picked, rows[s, at][None], s, 0)

        with scope("mla.attend"):
            picked = lax.fori_loop(0, count, trip, jnp.zeros(
                (b, n, k, rows.shape[-1]), rows.dtype))
        o = mla.attend_latent(
            q_nope.reshape((b * n, 1) + q_nope.shape[2:]),
            q_rope.reshape((b * n, 1) + q_rope.shape[2:]),
            picked.reshape(b * n, k, -1), valid.reshape(b * n, 1, k), p,
            dims=dims, cast=cast)
        return o.reshape((b, n) + o.shape[2:])

    def masked(q_nope, q_rope, mask):
        return mla.attend_latent(q_nope, q_rope, rows, mask, p, dims=dims,
                                 cast=cast)

    if isinstance(selection, tuple):
        return _by_query_blocks(gathered, q_nope, q_rope, *selection)
    return _by_query_blocks(masked, q_nope, q_rope, selection,
                            heads=q_nope.shape[2], keys=rows.shape[1])
