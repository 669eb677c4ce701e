"""Batched decode engine: the jitted programs behind the decode server.

A bounded program set serves any request stream, and the engine never
compiles outside it:

- ``("prefill", P_bucket)`` — one bucket-padded prompt forward ([1, P])
  through the SAME ``TransformerLM._block`` math as training, writing the
  per-layer K/V into one slot of the ``[L, S, T_max, Hkv, Dh]`` pool and
  sampling the request's first token from position ``prompt_len - 1``.
  One compile per prompt-ladder rung (``perf/bucketing.prompt_bucket``).
  A model with learned sparse attention (``TransformerLM(indexers=)``)
  prefills in blocks instead (``_serve_prefill_block_impl``): the rung's
  program takes ``PREFILL_BLOCK`` positions through every layer against
  the rows the blocks before it wrote, and the host runs it once for each
  block the prompt has — one pass over a 28k-token prompt would need its
  q, k and v at 64 heads of 256 (3 x 940 MB) beside the weights.
- ``("decode", S)`` — ONE step for ALL S slots at their own positions:
  scatter the consumed tokens' K/V at each slot's cursor, attend each row
  against its own masked cache history (GQA-aware — the pool stores
  ``num_kv_heads``), sample one token per row from per-slot RNG streams.
  One dispatch per token. Its inputs are the pool and the loop state
  (``SlotKVCache.loop``: cursors, last tokens, tokens owed, keys), all
  on the device, and it returns them advanced
  (``kv_cache.advance_loop``): step *n + 1* is dispatched from step
  *n*'s outputs before the host has read a token of them. A slot that
  owes nothing freezes itself (``remaining``): its token, cursor and key
  carry unchanged while its rows ride along computing garbage no one
  reads.
- ``("prefill", P_bucket, "admit")`` — the server's admission, ONE
  program a request (``_serve_admit_impl``, ``DecodeEngine.admit``): the
  prefill above, the request's key made from its seed inside it, and the
  slot's loop state written behind it (``kv_cache.slot_admit``: first
  token, cursor, tokens owed, key). Its arguments from the host are the
  padded prompt and one int32 vector, NumPy, transferred with the call:
  no eager one-op program runs beside it. The bare prefill stays for the
  hand-off's prefill replica (``fleet/replica.py``) and the compile
  rehearsals under ``benchmarks/tools/``.
- ``("slot_admit",)`` — the loop state's one write from the host
  (``kv_cache.slot_admit``): a request enters a slot after its hand-off
  or the last of its prefill blocks, or leaves it before its last token
  (the same write with nothing owed). One small program, which an
  engine's first admission runs once so that no release compiles it.
- ``("decode_spec", S)`` — a model with a multi-token-prediction module
  (``TransformerLM(mtp=)``) decodes in speculative rounds instead of
  plain steps, one round a dispatch (``_serve_mtp_impl``): the target
  verifies the token and the module's draft for the position after it
  with ONE two-candidate forward (``_serve_verify_impl``), accepts or
  resamples by the standard speculative-sampling rule
  (``_accept_round``) — greedy streams are token-identical to the
  model's greedy decode, sampled streams draw from its exact sampling
  distribution — and the module drafts for the next round. A round emits
  one token a slot, or two when the draft was accepted.

An engine thus dispatches ONE kind of decode block for its model: the
plain step, or the round of the model's own module.

The decode-family programs update the donated pool IN PLACE
(``_pool_attention``): every layer scatters its new rows straight into
the ``[L, S, T_max, Hkv, Dh]`` arrays the program was given
(``kv_cache.write_pool_rows``), and the pool a program returns is the
buffer that was donated to it — no slab is copied out to be written and
none is stacked back. Where a TPU is attached the layer's keys are read
from the pool itself by a Pallas kernel
(``pallas/decode_attention.py``), because
XLA:TPU copies ``pool[layer]`` out before a dot may read it — and only
the key blocks of the slots that owe a token (``remaining > 0``) are
read; elsewhere the XLA op attends to that view.

All program bodies are ``@traced`` hot roots
(``analysis/annotations.HOT_PATH_REGISTRY``) so dl4j-lint's host-sync
rule guards the decode loop: a ``float()`` / ``np.asarray`` slipped into
this module's program bodies is a lint finding, not a silent per-token
device sync. The one sanctioned readback is the per-dispatch token block
in ``server.py``.

Numerics contract (tests/test_serving.py): a slot's token sequence is
IDENTICAL to ``TransformerLM.generate`` on the same prompt — greedy and
sampled (each slot replays the exact ``sample``/``split`` chain of a
single-request ``generate(seed=...)``), and under greedy rounds. Slot
rows are computationally independent (every op is row-wise; masked pad
keys contribute exactly zero attention weight), so batching requests
changes no request's tokens.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.analysis.annotations import traced
from deeplearning4j_tpu.compile_cache import ensure_compile_cache
from deeplearning4j_tpu.pallas.flash_attention import flash_default_interpret
from deeplearning4j_tpu.perf.bucketing import (
    DEFAULT_PROMPT_BUCKETS, pad_prompt, prompt_bucket)
from deeplearning4j_tpu.scopes import scope
from deeplearning4j_tpu.serving.kv_cache import (
    SlotKVCache, advance_loop, attn_places, ring_positions, slot_admit,
    write_pool_rows)

__all__ = ["DecodeEngine"]


def _row_sampler(temperature: float, top_k: Optional[int]):
    """Per-row sampler ``(logits [V], key [2]) -> (tok, key)`` replaying
    the exact op sequence of ``make_generate``'s batch-of-one ``sample``
    (logits lifted to [1, V] so the categorical draw consumes the same
    random bits a single-request decode would). Filtering goes through
    ``_filtered_logits_fn`` — the SAME ops the speculative accept-ratio
    distributions use, so q(d) is by construction the probability the
    sampler draws ``d`` with (the two cannot drift)."""
    import jax
    import jax.numpy as jnp

    filt = (None if temperature == 0.0
            else _filtered_logits_fn(temperature, top_k))

    def one(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits[None], axis=-1)[0].astype(jnp.int32), \
                key
        scaled = filt(logits[None])
        key, sub = jax.random.split(key)
        return jax.random.categorical(sub, scaled, axis=-1)[0].astype(
            jnp.int32), key

    return one


def _filtered_logits_fn(temperature: float, top_k: Optional[int]):
    """Vectorized ``logits [..., V] -> filtered scaled logits`` — the
    argument ``sample``'s categorical draws from, shared by the row
    sampler and a round's accept-ratio distributions."""
    import jax.numpy as jnp
    from jax import lax

    def f(logits):
        scaled = logits / temperature
        if top_k is not None:
            kth = lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
        return scaled

    return f


@traced
def _serve_prefill_impl(model, sample_row, quantized, params, kv,
                        prompt, prompt_len, slot, key):
    """Prefill one bucket-padded prompt ([1, P]) into pool slot ``slot``.
    Returns ``(token, key, pool)``, and for a model with routed experts
    ``(token, key, pool, routing)`` (``_stack_routing``, rows = the P
    positions).

    Causality makes the pad tail inert: position ``i < prompt_len``
    attends keys ``0..i`` — all real tokens — so the K/V written at real
    positions (and the ``prompt_len - 1`` hidden state the first token is
    sampled from) are the unpadded prefill's values. ``prompt_len`` and
    ``slot`` are traced: one compile per bucket, not per request.

    ``quantized`` selects nothing and must be False: the tools under
    ``benchmarks/`` bind it third (ROADMAP D12)."""
    import jax.numpy as jnp
    from jax import lax

    if quantized is not False:
        raise ValueError(
            f"quantized={quantized!r}: the int8 pool left the serving path "
            "(no kernel read, no cell: ISSUE 43); pass False")
    policy = model.policy
    cdt = policy.compute_dtype
    p = prompt.shape[1]
    with scope("lm.embed"):
        h = jnp.take(params["embed"], prompt, axis=0)
        if model.pos_encoding == "learned":
            h = h + params["pos"][:p][None]
        h = policy.cast_compute(h)
    h = model._enter(h)
    ks, vs = [], []
    # the other layer kinds
    left = {"latent": [], "kda": [], "conv": [], "norm": []}
    # the pad tail holds no token: it takes no part in routed experts and
    # moves no recurrent state (a 'kda' or 'gdn' layer's state is as of
    # prompt_len)
    live = ((jnp.arange(p) < prompt_len)[None]
            if model.num_experts or model.hybrid else None)
    moe_info: list = []
    for i, blk in enumerate(params["blocks"]):
        h, kk, vv = model._block(blk, h, live=live, moe_info=moe_info,
                                 layer=i)
        if "kda" in blk or "gdn" in blk:
            left["kda"].append(kk)
            left["conv"].append(vv)
        elif "ret" in blk:
            left["kda"].append(kk)
            left["norm"].append(vv)
        elif "mla" in blk:
            left["latent"].append(kk)
        else:
            ks.append(kk.astype(cdt))
            vs.append(vv.astype(cdt))
    # a 'kda' or 'gdn' layer's [1, H, dk, dk] state and [1, K-1, C] tail, a
    # 'ret' layer's [1, Hkv, D, dh] state and [1, Hkv, dh, dh] normaliser and
    # an 'mla' layer's [1, P, r + dr] rows, each into the slot's place in its
    # layer's own array: the slot's old state is overwritten whole
    def into(pool, new):
        if new.shape[-1] < pool.shape[-1]:      # a latent row's zero lanes
            new = jnp.pad(new, ((0, 0),) * (new.ndim - 1) + (
                (0, pool.shape[-1] - new.shape[-1]),))
        return lax.dynamic_update_slice(
            pool, new.astype(pool.dtype), (slot,) + (0,) * (pool.ndim - 1))

    new_kv = {name: [into(pool, new) for pool, new in zip(kv[name], rows)]
              for name, rows in left.items() if rows}
    def put(pool, cat):
        cat = cat.astype(pool.dtype)
        if pool.ndim == 4:      # rows of wide heads (``pool_shape``)
            cat = cat.reshape(cat.shape[:2] + (-1, cat.shape[-1]))
        return lax.dynamic_update_slice(
            pool, cat, (0, slot) + (0,) * (pool.ndim - 2))

    # each 'attn' layer's rows into its own pool: the T_max rows of ``k`` and
    # ``v``, or, for a layer with a window of a model that keeps a ring
    # (``kw``, ``vw``), the whole ring: row r takes the position it holds once
    # the prompt's last token is written (``ring_positions``; a row that
    # holds none yet takes position 0, which the mask never admits)
    places = [name for name, _ in attn_places(model, "kw" in kv)]
    for name, kn, vn in (("kv", "k", "v"), ("ring", "kw", "vw")):
        mine = [j for j, n in enumerate(places) if n == name]
        if not mine:
            continue
        kcat = jnp.stack([ks[j] for j in mine])     # [L, 1, P, Hkv, Dh]
        vcat = jnp.stack([vs[j] for j in mine])
        if name == "ring":
            r = _logical_dims(kv[kn], model.num_kv_heads)[2]
            held = jnp.clip(ring_positions(prompt_len - 1, r), 0, p - 1)
            kcat, vcat = (jnp.take(cat, held, axis=2) for cat in (kcat, vcat))
        new_kv.update({kn: put(kv[kn], kcat), vn: put(kv[vn], vcat)})
    h_last = jnp.take(h[0], prompt_len - 1, axis=0)        # [D] ([n, D])
    logits = model._unembed(params, h_last)
    with scope("lm.head"):
        tok, key = sample_row(logits, key)
    if model.num_experts:
        return tok, key, new_kv, _stack_routing(moe_info)
    return tok, key, new_kv


@traced
def _serve_admit_impl(model, sample_row, params, kv, loop, prompt, at):
    """The server's admission as ONE program: ``_serve_prefill_impl`` of
    the bucket-padded prompt ([1, P]) and then the slot's loop state
    (``slot_admit``), from ``at`` = ``[slot, prompt_len, remaining, seed]``
    (int32, the one small transfer an admission makes beside its prompt:
    ``slot_admit``'s own vector and the seed): the key is made here from
    the request's seed — the bits ``jax.random.PRNGKey(seed)`` gives on the
    host — and the slot takes the first token, the cursor ``prompt_len``,
    the tokens it is still owed and the key as the sampler left it.
    Returns ``(token, loop, pool)`` and, for a model with routed experts,
    ``(token, loop, pool, routing)``."""
    import jax

    tok, key, new_kv, *record = _serve_prefill_impl(
        model, sample_row, False, params, kv, prompt, at[1], at[0],
        jax.random.PRNGKey(at[3]))
    return (tok, slot_admit(loop, at, tok, key), new_kv) + tuple(record)


def _stack_routing(moe_info):
    """The layers' routing (``routed_experts.routed_ffn``'s ``info``, as
    ``TransformerLM._block`` collects it) as ONE int32 array a serving
    program returns beside its tokens, so that the host reads it in a
    single transfer: ``[L, E + 2 N k + 2]``, per layer the load ``[E]`` (the
    live (token, expert) pairs each expert received), then the N rows'
    experts ``[N k]``, then the bits of their float32 weights ``[N k]``
    (0 on a row that holds no token), then how many experts' matrices the
    layer fetched (``info["read"]``) and how many sorted rows its passes
    took (``info["run"]``). ``unpack_routing`` is its inverse."""
    import jax.numpy as jnp
    from jax import lax

    def layer(info):
        weights = lax.bitcast_convert_type(info["weights"], jnp.int32)
        return jnp.concatenate([info["load"], info["experts"].reshape(-1),
                                weights.reshape(-1), info["read"][None],
                                info["run"][None]])

    return jnp.stack([layer(info) for info in moe_info])


def unpack_routing(packed, num_experts: int, experts_per_token: int):
    """``_stack_routing``'s array on the host (numpy) -> ``(load [L, E]
    int32, experts [L, N, k] int32, weights [L, N, k] float32, read [L]
    int32, run [L] int32)``, views."""
    layers = packed.shape[0]
    load, rows = packed[:, :num_experts], packed[:, num_experts:-2]
    half = rows.shape[1] // 2
    shape = (layers, -1, experts_per_token)
    return (load, rows[:, :half].reshape(shape),
            rows[:, half:].view(np.float32).reshape(shape), packed[:, -2],
            packed[:, -1])


def _logical_dims(pool, hkv: int):
    """``(L, S, T, Hkv, Dh)`` of a K (or V) pool or ring, whichever shape it
    is stored in: rows of wide heads are stored flat
    (``kv_cache.pool_shape``)."""
    return pool.shape if pool.ndim == 5 else (
        pool.shape[:2] + (pool.shape[2] // hkv, hkv, pool.shape[3]))


@traced
def _pool_attention(model, pool, positions, pool_kernel, live=None):
    """``li -> attention(q, kk, vv)`` for a decode-family forward over
    the slot pool: layer ``li`` scatters its new K/V rows at
    ``positions [S, Q]`` straight into the carried pool
    (``write_pool_rows``), then query ``(s, i)`` attends that layer's
    keys ``<= positions[s, i]`` of slot ``s`` (window-clipped like
    training). ``pool`` is the caller's own dict of the pool arrays;
    every layer rebinds its entries, so after the layer loop it holds the
    program's output pool — the arrays it was given, updated in place
    when the program's pool argument is donated. Nothing here stacks
    slabs back into a pool. A model that gives a window a layer
    (``attn['windows']``) meets each layer with its own window, and where it
    keeps a ring (``pool`` holds ``kw``, ``vw``: ``kv_cache.attn_places``) a
    window layer writes its row at ``position mod R`` of the ring and attends
    the positions the ring's rows hold (``ring_positions``).

    The read has two forms. XLA:TPU copies ``pool[li]`` out as a slab
    before any dot may use it, so where the Pallas kernel applies
    (``pool_kernel``: a TPU is attached unless the caller says otherwise;
    a pool whose head size fills the lanes)
    ``pool_decode_attention`` reads from the pool itself the key blocks
    of the slots that hold a request (``live [S]``, bool; ``None``: all
    of them) — a slot that holds none is neither fetched nor multiplied
    and its rows are zeros. Everywhere else — the CPU, a pool sharded
    over a mesh — the XLA op attends to the ``pool[li]`` view, every
    slot's."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.attention import grouped_query_attention
    from deeplearning4j_tpu.pallas import decode_attention as kernel

    dtype = model.policy.compute_dtype
    rows = jnp.arange(positions.shape[0])
    if "k" not in pool and "kw" not in pool:
        return None             # no layer of this model keeps keys
    if pool_kernel is None:
        pool_kernel = not flash_default_interpret()
    if "kw" in pool and positions.shape[1] != 1:
        raise NotImplementedError(
            "a ring of rows (a model with attn['windows']) is read by one "
            "query a slot: a round's candidates would each need the ring as "
            "of their own position")
    # the layers' places in the pools and their windows, in the layers' order
    places = attn_places(model, "kw" in pool)
    windows = [model.windows[i] for i in model.layers_of("attn")]
    hkv = model.num_kv_heads
    # per pool: its arrays' names, its logical axes, the kernel's rows a key
    # block (None: the XLA read)
    pools = {}
    for name, kn, vn in (("kv", "k", "v"), ("ring", "kw", "vw")):
        if kn in pool:
            dims = _logical_dims(pool[kn], hkv)
            pools[name] = (kn, vn, dims, kernel.pool_block_rows(
                dims, pool[kn].dtype) if pool_kernel else None)
    # the XLA read's masks [S, Q, rows], one a (pool, window)
    masks = {}
    for (name, _), window in zip(places, windows):
        _, _, dims, block = pools[name]
        if block is not None or (name, window) in masks:
            continue
        if name == "ring":
            keys = ring_positions(positions, dims[2])
            mask = keys >= 0
        else:
            keys = jnp.arange(dims[2])
            mask = keys <= positions[:, :, None]
        if window is not None:
            mask &= keys > positions[:, :, None] - window
        masks[name, window] = mask

    def layer(li):
        (name, place), window = places[li], windows[li]
        kn, vn, dims, block = pools[name]
        ring = name == "ring"

        def attn(q, kk, vv):
            with scope("kv.write"):
                at = positions % dims[2] if ring else positions
                for n, new in ((kn, kk), (vn, vv)):
                    pool[n] = write_pool_rows(pool[n], place, new, rows, at)
            if block is not None:
                return kernel.pool_decode_attention(
                    q, pool[kn], pool[vn], place, positions, window=window,
                    block_rows=block, interpret=flash_default_interpret(),
                    live=live, hkv=hkv, ring=ring,
                    name="attn.window" if model.by_layer
                    and window is not None else None)
            views = ((pool[n][place] if pool[n].ndim == 5
                      else pool[n][place].reshape(dims[1:])).astype(dtype)
                     for n in (kn, vn))
            return grouped_query_attention(q, *views,
                                           mask=masks[name, window])
        return attn

    return layer


def _write_rows(cache, new, rows, positions, slot):
    """``new`` [b, Q, w] into ``cache`` [S, T_max, W >= w] (zeros in the
    lanes past w): one row a slot and query at ``positions [S, Q]`` (a
    decode step: ``rows`` = every slot), or, with ``slot``, the Q
    consecutive rows of that one slot from ``positions[0, 0]`` on (a
    prefill block)."""
    import jax.numpy as jnp
    from jax import lax

    new = jnp.pad(new, ((0, 0), (0, 0), (0, cache.shape[-1] - new.shape[-1]))
                  ).astype(cache.dtype)
    if slot is None:
        return cache.at[rows[:, None], positions].set(new)
    return lax.dynamic_update_slice(cache, new, (slot, positions[0, 0], 0))


def _slot_rows(cache, slot, keys):
    """What the queries may read of ``cache`` [S, T_max, W]: all of it (a
    decode step: every slot its own rows), or the first ``keys`` rows of
    ``slot`` as [1, keys, W] (a prefill block: no position of the rung lies
    beyond them)."""
    from jax import lax

    if slot is None:
        return cache
    return lax.dynamic_slice(cache, (slot, 0, 0), (1, keys, cache.shape[2]))


def _latent_attention(model, pool, positions, slot=None, keys=None,
                      live=None):
    """``(j, p) -> attention(q_nope, q_rope, latent[, selection])`` for a
    decode-family forward of an ``mla`` layer (the model's j-th, parameters
    ``p``) over its cached latent rows: the new rows land at ``positions
    [S, Q]`` of ``pool["latent"][j]`` (``[S, T_max, latent_row_width]``,
    rebound in ``pool`` as ``_pool_attention`` rebinds the K/V pools), then
    query ``(s, i)`` attends slot ``s``'s rows ``<= positions[s, i]`` in the
    absorbed form (``models/mla.attend_latent``) — or, handed a
    ``selection`` (``models/dsa.select``'s), the rows it names and no
    others (``dsa.attend_selected``), which are fetched for the slots that
    owe a token alone (``live [S]``, bool; None: all of them — the work list
    of ``dsa.live_slots``); the others' outputs are finite and read by
    nobody. ``slot`` and ``keys``: the forward is a prefill block of that one
    slot (``_write_rows``, ``_slot_rows``)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import dsa, mla

    rows = jnp.arange(positions.shape[0])
    cast = model.policy.cast_compute

    def layer(j, p):
        def attn(q_nope, q_rope, latent, selection=None):
            with scope("kv.write"):
                cache = _write_rows(pool["latent"][j], latent, rows,
                                    positions, slot)
            pool["latent"][j] = cache
            view = _slot_rows(cache, slot, keys)
            if selection is not None:
                return dsa.attend_selected(q_nope, q_rope, view, selection,
                                           p, dims=model.mla, cast=cast,
                                           live=live)
            mask = jnp.arange(view.shape[1]) <= positions[:, :, None]
            # a prefill block's queries mla.QUERY_BLOCK at a time: 2,048 of
            # them against 4,096 rows at 64 heads are 2 GiB of scores
            return mla.by_query_blocks(
                lambda qn, qr, m: mla.attend_latent(
                    qn, qr, view, m, p, dims=model.mla, cast=cast),
                q_nope, q_rope, mask)
        return attn

    return layer


def _index_selection(model, pool, positions, slot=None, keys=None,
                     live=None, prompt_len=None):
    """``j -> indexer(q^I, k^I, w)`` for a decode-family forward of the
    model's j-th layer with a lightning indexer, over its cached index
    keys: the new keys land at ``positions [S, Q]`` of ``pool["index"][j]``
    (``[S, T_max, dI]``) as the latent rows do, then each query scores its
    slot's keys ``<= positions[s, i]`` and selects (``models/dsa.select``).
    A slot that owes no token (``live [S]`` false) queries from position -1:
    no key lies behind it, so its selection is empty and ``dsa.select``
    reads none of its keys. ``slot`` and ``keys`` as
    ``_latent_attention``'s.

    With pooled index keys (``dsa["pool"]`` = n; ``kv_cache``'s ``index``
    and ``index_open``) what is cached is a mean key a pool. A decode step
    adds its key to the slot's open pool's running sum (from zero where the
    position opens a pool) and writes ``sum / n`` at the pool's row: the
    pool's key once its last position has come, and before that a row no
    query scores. A prefill block (its start a multiple of n) writes the
    means of its own pools and leaves the sum as of ``prompt_len``, the
    keys of the prompt's last, open pool. A slot that owes no token changes
    neither. Scope ``dsa.pool``."""
    import jax.numpy as jnp
    from jax import lax
    from deeplearning4j_tpu.models import dsa

    rows = jnp.arange(positions.shape[0])
    n = model.dsa.get("pool", 1) if model.dsa else 1

    def pooled(j):
        def indexer(iq, ik, iw):
            cache, open_ = pool["index"][j], pool["index_open"][j]
            with scope("dsa.pool"):
                if slot is None:
                    at = positions[:, 0]
                    key = ik[:, 0].astype(jnp.float32)
                    total = jnp.where((at % n == 0)[:, None], 0.0,
                                      open_) + key
                    owes = jnp.ones_like(at, bool) if live is None else live
                    pool["index_open"][j] = jnp.where(owes[:, None], total,
                                                      open_)
                    # a slot that owes nothing writes beyond the rows: dropped
                    cache = cache.at[rows, jnp.where(
                        owes, at // n, cache.shape[1])].set(
                            (total / n).astype(cache.dtype), mode="drop")
                else:
                    means = dsa.pool_keys(ik, n)
                    cache = lax.dynamic_update_slice(
                        cache, means.astype(cache.dtype),
                        (slot, positions[0, 0] // n, 0))
                    last = ((positions[0] >= prompt_len // n * n)
                            & (positions[0] < prompt_len))
                    pool["index_open"][j] = lax.dynamic_update_slice(
                        open_, jnp.sum(jnp.where(
                            last[:, None], ik[0].astype(jnp.float32), 0.0),
                            axis=0)[None], (slot, 0))
            pool["index"][j] = cache
            q_pos = positions if live is None else jnp.where(
                live[:, None], positions, -1)
            view = cache if slot is None else lax.dynamic_slice(
                cache, (slot, 0, 0), (1, keys // n, cache.shape[2]))
            return dsa.select(iq, iw, view, q_pos, model.dsa["topk"], pool=n)
        return indexer

    if n > 1:
        return pooled

    def layer(j):
        def indexer(iq, ik, iw):
            with scope("dsa.index"):
                cache = _write_rows(pool["index"][j], ik, rows, positions,
                                    slot)
                q_pos = positions if live is None else jnp.where(
                    live[:, None], positions, -1)
            pool["index"][j] = cache
            return dsa.select(iq, iw, _slot_rows(cache, slot, keys),
                              q_pos, model.dsa["topk"])
        return indexer

    return layer


def _latent_layers(model, params, pool, positions, slot=None, keys=None,
                   live=None, prompt_len=None):
    """Per block of ``params`` the keywords ``TransformerLM._block`` takes
    for an ``mla`` layer served from the pool (``attention`` and, for a
    layer with an indexer, ``indexer``; None for another kind of layer)."""
    attention = _latent_attention(model, pool, positions, slot, keys, live)
    indexer = _index_selection(model, pool, positions, slot, keys, live,
                               prompt_len)
    out, j, jf = [], 0, 0
    for blk in params["blocks"]:
        if "mla" not in blk:
            out.append(None)
            continue
        kw = {"attention": attention(j, blk["mla"])}
        j += 1
        if "indexer" in blk["mla"]:
            kw["indexer"] = indexer(jf)
            jf += 1
        out.append(kw)
    return out


def _stack_selection(selections, k, at=None):
    """The selections of the layers with an indexer (``dsa.select``'s,
    either form) as ONE int32 array ``[layers, b, Q, k]`` of positions, -1
    where a row selected fewer than k (``dsa.selected_positions``); with
    ``at`` (traced), of that one query of the first row alone: ``[layers,
    k]``."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import dsa

    def cut(a):
        return a if at is None else jnp.take(a[:1], at[None], axis=1)

    out = jnp.stack([dsa.selected_positions(
        jax.tree_util.tree_map(cut, sel), k) for sel in selections])
    return out if at is None else out[:, 0, 0]


# positions a prefill block of a model with learned sparse attention takes
# through the layers at once. Every block reads the weights once more
# (10.7 GB as stored at GLM-5.2's cut: 13 ms) and computes 2.6 GFLOP a
# position (13 ms at the chip's peak per 1,024), and from 1,025 rows the
# routed experts take their sorted form, which multiplies only the (token,
# expert) pairs that landed here (``routed_experts.DENSE_MAX_TOKENS``).
PREFILL_BLOCK = 2048


def prefill_block_count(prompt_len: int, bucket: int) -> int:
    """Blocks a prompt on the rung ``bucket`` is prefilled in
    (``_serve_prefill_block_impl``): ``PREFILL_BLOCK`` positions each, or
    the whole rung where it is no multiple of that."""
    c = PREFILL_BLOCK if bucket % PREFILL_BLOCK == 0 else bucket
    return -(-prompt_len // c)


def prefill_carry_layout(model, bucket: int) -> dict:
    """``{name: (shape, dtype name, fill)}`` of what the blocks of one
    prefill hand on beside the pool (``_serve_prefill_block_impl``)."""
    import jax.numpy as jnp

    n_moe, k = model.n_layers("moe"), model.experts_per_token
    from deeplearning4j_tpu.models import dsa

    topk = dsa.selection_width(model.dsa, bucket) if model.dsa else 0
    streams = (int(model.hc["streams"]),) if model.hc else ()
    return {
        "h_last": (streams + (model.d_model,),
                   jnp.dtype(model.policy.compute_dtype).name, 0),
        "sel_last": ((model.indexers.count("full"), topk), "int32", -1),
        "load": ((n_moe, model.experts_held), "int32", 0),
        "read": ((n_moe,), "int32", 0),
        "run": ((n_moe,), "int32", 0),
        "chosen": ((n_moe, bucket, k), "int32", 0),
        "weights": ((n_moe, bucket, k), "float32", 0)}


@traced
def _serve_prefill_block_impl(model, sample_row, params, kv, carry, prompt,
                              prompt_len, slot, key, i):
    """``_serve_prefill_impl`` for a model with learned sparse attention,
    whose prefill cannot be one pass (the module's docstring): block ``i``
    of the bucket-padded prompt ([1, P]) through every layer. The host runs
    ``prefill_block_count`` of them one after another (``DecodeEngine.
    prefill``), so the rung's pad blocks are never run, and one block's
    temporaries are all the program holds beside weights and pool. (As one
    program with a loop over the blocks, XLA hoists the loop-invariant
    bf16 copies of every float32 weight out of the loop: 5.3 GB live at
    once, 18.9 GB in all at GLM-5.2's cut.)

    A block is the decode-family forward at Q = its positions: it writes
    its latent rows and index keys into the slot, then every query scores
    the slot's index keys up to its own position, selects, and attends the
    selected rows (``_latent_layers``). A ``kda`` layer between them takes
    the block as a prefill takes a prompt, from the slot's recurrent matrix
    and convolution tail as the block before left them (``block 0``: zeros)
    and writes both back. The pad tail of the last block is
    inert by causality and writes rows beyond the cursor, which the decode
    steps overwrite before any query reads them.

    A model that drafts from its own multi-token-prediction module
    (``TransformerLM(mtp=)``, no indexer: every query attends the slot's rows
    up to its own) is prefilled the same way, and its module runs over the
    block too, into its own layer of latent rows (the last), and proposes the
    first round's draft.

    ``carry`` (``prefill_carry_layout``; block 0 resets it) hands on the
    routing of the blocks so far and, from the block that holds position
    ``prompt_len - 1``, its hidden state and selections. Returns ``(token,
    key, pool, carry, routing, selection)`` (for a model with a module
    ``(token, key, pool, carry, routing, draft)``), of which the last block's
    token, key, routing and selection are the prefill's: ``routing`` as
    ``_stack_routing`` packs it (rows = the P positions, those of blocks
    not run zero; None without routed experts), ``selection`` [layers with
    an indexer, k] int32, the positions that the prompt's last token
    selected (-1: fewer than k)."""
    import jax.numpy as jnp
    from jax import lax

    if set(model.mixers) - {"mla", "kda"}:
        raise NotImplementedError(
            "prefill in query blocks is written for 'mla' layers and, "
            "between them, 'kda' layers, whose recurrence a block continues; "
            f"this model's are {model.mixers}")
    policy = model.policy
    p = prompt.shape[1]
    c = p // prefill_block_count(p, p)
    layout = prefill_carry_layout(model, p)
    carry = {name: jnp.where(i == 0, jnp.full_like(a, layout[name][2]), a)
             for name, a in carry.items()}
    pool = {name: list(v) for name, v in kv.items()}
    start = i * c
    positions = (start + jnp.arange(c))[None]                   # [1, c]
    toks = lax.dynamic_slice(prompt, (0, start), (1, c))
    with scope("lm.embed"):
        h = policy.cast_compute(jnp.take(params["embed"], toks, axis=0))
    h = model._enter(h)
    live = positions < prompt_len
    moe_info: list = []
    selections, selection = [], None
    recurrent = 0       # the 'kda' layers seen: their place in the state
    for blk, kw in zip(params["blocks"], _latent_layers(
            model, params, pool, positions, slot, p,
            prompt_len=prompt_len)):
        if kw is None:
            # a 'kda' layer of block i continues from what the blocks
            # before left in the slot, matrix and convolution tail (block
            # 0: from a request's start), and leaves its own there; the pad
            # tail moves neither (``live``)
            j, recurrent = recurrent, recurrent + 1
            state = tuple(
                jnp.where(i == 0, 0, lax.dynamic_slice_in_dim(a, slot, 1))
                .astype(a.dtype) for a in (pool["kda"][j], pool["conv"][j]))
            h, *state = model._block(blk, h, live=live, moe_info=moe_info,
                                     state=state)
            for name, a in zip(("kda", "conv"), state):
                pool[name][j] = lax.dynamic_update_slice_in_dim(
                    pool[name][j], a.astype(pool[name][j].dtype), slot, 0)
            continue
        h, _, selection = model._block(
            blk, h, positions=positions, live=live, moe_info=moe_info,
            selection=selection, **kw)
        if "indexer" in kw:
            selections.append(selection)
    at = prompt_len - 1 - start
    here = (at >= 0) & (at < c)
    at = jnp.clip(at, 0, c - 1)
    new = {"h_last": jnp.where(here, jnp.take(h[0], at, axis=0),
                               carry["h_last"])}
    if model.dsa:
        new["sel_last"] = jnp.where(
            here, _stack_selection(selections, layout["sel_last"][0][1], at),
            carry["sel_last"])
    logits = model._unembed(params, new["h_last"])
    with scope("lm.head"):
        tok, key = sample_row(logits, key)
    draft = ()
    if model.mtp:
        # the module one position behind: its row at position i reads the
        # hidden state there and token i + 1, the prompt's next or, at the
        # prompt's last position, the token just sampled; its argmax there
        # is the first round's draft
        nxt = lax.dynamic_slice(jnp.pad(prompt, ((0, 0), (0, 1))),
                                (0, start + 1), (1, c))
        nxt = jnp.where(positions == prompt_len - 1, tok, nxt)
        attention = _latent_attention(model, pool, positions, slot, p)(
            len(model.layers_of("mla")), params["mtp"]["block"]["mla"])
        hm, _, _ = model.mtp_block(
            params, model.mtp_input(params, model._norm(h, params["ln_f"]),
                                    nxt),
            positions=positions, live=live, moe_info=moe_info,
            attention=attention)
        draft = (jnp.argmax(model.mtp_head(
            params, jnp.take(hm[0], at, axis=0))).astype(jnp.int32),)
    routing = None
    if moe_info:
        new["load"] = carry["load"] + jnp.stack(
            [m["load"] for m in moe_info])
        new["read"] = jnp.maximum(carry["read"], jnp.stack(
            [m["read"] for m in moe_info]))
        new["run"] = carry["run"] + jnp.stack([m["run"] for m in moe_info])
        for name, got in (("chosen", "experts"), ("weights", "weights")):
            new[name] = lax.dynamic_update_slice(
                carry[name], jnp.stack([m[got] for m in moe_info]),
                (0, start, 0))
        n_moe = len(moe_info)
        routing = jnp.concatenate(
            [new["load"], new["chosen"].reshape(n_moe, -1),
             lax.bitcast_convert_type(new["weights"], jnp.int32).reshape(
                 n_moe, -1), new["read"][:, None], new["run"][:, None]],
            axis=1)
    new = {**carry, **new}
    if model.dsa:
        return tok, key, pool, new, routing, new["sel_last"]
    return (tok, key, pool, new, routing) + draft


def _decode_step_body(model, params, kv, tok, positions, *,
                      pool_kernel=None, live=None, moe_info=None,
                      selections=None):
    """ONE decode forward for all S slots: consume ``tok[s]`` at
    ``positions[s]``, write its K/V at that cursor,
    attend keys ``<= positions[s]`` (``_pool_attention``).
    Returns ``(logits [S, V], new_kv)`` — sampling happens in the
    caller. ``new_kv`` is ``kv`` with one row per slot and layer scattered in:
    donated, it is the same buffer. Free
    slots ride along computing garbage no one reads — their rows are
    masked out of nothing (rows are independent) and their pool writes
    land at frozen cursors the admission prefill overwrites. ``live
    [S]`` (bool) names the slots that hold a request: the pool kernel
    reads none of the others' keys, a model with learned sparse attention
    neither their index keys nor their selected latent rows
    (``_latent_layers``), and they choose no routed expert and count in no
    load. ``moe_info`` receives each layer's routing
    (``TransformerLM._block``).

    Each layer meets its own kind of state, at its place among the layers
    of its kind: an ``attn`` layer the K/V pools, an ``mla`` layer its
    latent rows (``_latent_attention``), a ``kda`` or ``gdn`` layer its
    recurrent matrix and convolution tail (one list each, in the layers'
    order), which it takes and hands back advanced for the live slots and
    untouched for the others: where ``kda.recur`` takes
    ``pallas/delta_step.py`` it reads and writes the live slots' matrices
    only, in place in the donated pool (``pool_kernel`` False, a pool over a
    mesh: ``kda_step`` over every slot's); a ``ret`` layer its state (in the
    recurrent matrices' list) and its normaliser (a list of their own)
    likewise, through ``pallas/retention_step.py``. In a model with
    learned sparse attention a layer with an indexer also meets its index
    keys, selects, and its selection goes to the layers after it
    (``_latent_layers``); ``selections`` receives each such layer's."""
    import jax.numpy as jnp

    with scope("lm.embed"):
        h = jnp.take(params["embed"], tok, axis=0)         # [S, D]
        if model.pos_encoding == "learned":
            h = h + params["pos"][positions]
        h = model.policy.cast_compute(h)[:, None, :]       # [S, 1, D]
    h = model._enter(h)     # hyper-connections: [S, 1, n, D]
    new_kv = {k: list(v) if isinstance(v, list) else v
              for k, v in kv.items()}
    cached_attention = _pool_attention(
        model, new_kv, positions[:, None], pool_kernel, live)
    latent = _latent_layers(model, params, new_kv, positions[:, None],
                            live=live)
    # a layer's place in its kind's state; a recurrent layer's matrix lies
    # among all of them ("kda"), its other half among its own kind's
    seen = {"attn": 0, "mla": 0, "kda": 0, "conv": 0, "norm": 0}
    selection = None
    for i, (blk, kw) in enumerate(zip(params["blocks"], latent)):
        kind = ("kda" if "gdn" in blk or "ret" in blk
                else next(k for k in ("attn", "mla", "kda") if k in blk))
        j = seen[kind]
        seen[kind] += 1
        if kind == "kda":
            other = "norm" if "ret" in blk else "conv"
            jo = seen[other]
            seen[other] += 1
            # the recurrence's kernel follows the pool kernel's rule: not
            # over a pool sharded over a mesh (``_decode_jit``); with no
            # TPU attached it runs through the Pallas interpreter
            kw = {"state": (new_kv["kda"][j], new_kv[other][jo]),
                  "state_kernel": pool_kernel is not False}
        elif kind == "mla":
            kw = dict(kw, selection=selection)
        else:
            kw = {"attention": cached_attention(j)}
        h, a, b = model._block(
            blk, h, positions=positions[:, None], moe_info=moe_info,
            live=None if live is None else live[:, None], layer=i, **kw)
        if kind == "kda":
            new_kv["kda"][j], new_kv[other][jo] = a, b
        elif kind == "mla" and model.dsa:
            selection = b
            if selections is not None and "indexer" in kw:
                selections.append(b)
    logits = model._unembed(params, h[:, 0])               # [S, V]
    return logits, new_kv


@traced
def _serve_decode_impl(model, sample_row, params, kv, tok, positions,
                       keys, live=None, *, pool_kernel=None):
    """The PR-10 single-step program: one batched forward + per-slot
    sampling. One host dispatch per token.
    Returns ``(tokens, keys, pool)``, and for a model with routed experts
    ``(tokens, keys, pool, routing)`` (``_stack_routing``, rows = the S
    slots), and for one with learned sparse attention a fifth value, the
    step's selections ``[layers with an indexer, S, k]`` int32
    (``_stack_selection``)."""
    import jax

    moe_info: list = []
    selections: list = []
    logits, new_kv = _decode_step_body(model, params, kv, tok, positions,
                                       pool_kernel=pool_kernel, live=live,
                                       moe_info=moe_info,
                                       selections=selections)
    with scope("lm.head"):
        toks, keys = jax.vmap(sample_row)(logits, keys)
    routing = _stack_routing(moe_info) if model.num_experts else None
    if model.dsa:
        from deeplearning4j_tpu.models import dsa

        k = dsa.selection_width(model.dsa, new_kv["latent"][0].shape[1])
        return (toks, keys, new_kv, routing,
                _stack_selection(selections, k)[:, :, 0])
    if model.num_experts:
        return toks, keys, new_kv, routing
    return toks, keys, new_kv


@traced
def _serve_decode_loop_impl(model, sample_row, params, kv, loop, *,
                            pool_kernel=None):
    """The plain decode program: the PR-10 step on the device's own
    loop state. ``loop`` is ``SlotKVCache.loop``; a
    slot is live while it owes a token (``remaining > 0`` — the pool
    read's and the routed experts' ``live`` mask), consumes ``tok`` at
    its cursor and takes the sampled token; ``advance_loop`` moves the
    state on. Returns ``(loop, pool)`` and, for a model with routed
    experts, ``(loop, pool, routing)`` (with learned sparse attention,
    ``(loop, pool, routing, selection)``): the token block of the step is
    ``loop["tok"]``."""
    out = _serve_decode_impl(
        model, sample_row, params, kv, loop["tok"], loop["cursors"],
        loop["keys"], loop["remaining"] > 0, pool_kernel=pool_kernel)
    return (advance_loop(loop, out[0], out[1]),) + tuple(out[2:])


@traced
def _serve_verify_impl(model, params, kv, toks, positions, live=None, *,
                       pool_kernel=None, moe_info=None, hidden=False):
    """Multi-token target forward for a round's verify: consume
    ``toks [S, Q]`` at per-row ``positions [S, Q]`` against the slot
    pool, scatter-writing every candidate's K/V (an ``mla`` layer's: its
    latent row, ``_latent_layers``) at its position (the
    accepted prefix becomes permanent; rejected tails sit beyond the
    rewound cursor, masked until overwritten). Per-query masks keep
    causality at ragged per-slot offsets: query q attends pool keys
    ``<= positions[s, q]``; the pool kernel reads the ``live [S]`` slots'
    keys only. Returns ``(logits [S, Q, V], new_kv)``; with ``hidden``
    also the last block's output [S, Q, D], before the final norm. A list
    passed as ``moe_info`` receives each layer's routing (rows = the S Q
    candidates), and the rows of a slot that is not ``live`` then choose
    no routed expert."""
    import jax.numpy as jnp

    with scope("lm.embed"):
        h = jnp.take(params["embed"], toks, axis=0)        # [S, Q, D]
        if model.pos_encoding == "learned":
            h = h + params["pos"][positions]
        h = model.policy.cast_compute(h)
    h = model._enter(h)
    new_kv = {k: list(v) if isinstance(v, list) else v
              for k, v in kv.items()}
    cached_attention = _pool_attention(model, new_kv, positions, pool_kernel,
                                       live)
    rows = (None if live is None or moe_info is None
            else jnp.broadcast_to(live[:, None], positions.shape))
    li = 0
    for blk, kw in zip(params["blocks"], _latent_layers(
            model, params, new_kv, positions, live=live)):
        if kw is None:
            kw = {"attention": cached_attention(li)}
            li += 1
        h, _, _ = model._block(blk, h, positions=positions, live=rows,
                               moe_info=moe_info, **kw)
    logits = model._unembed(params, h)                     # [S, Q, V]
    return (logits, new_kv, h) if hidden else (logits, new_kv)


def _accept_round(act, logits, d, q, keys, gamma, greedy, sample_filtered):
    """A speculative round's accept / resample rule, general in the
    number of proposals ``gamma`` (the model's own module proposes one):
    ``logits [S, G + 1, V]`` the target's at the candidates'
    positions, ``d [S, G]`` the proposals, ``q [S, G, V]`` the distributions
    they were drawn from (unused when greedy; one-hot for a proposal that
    was an argmax), ``keys`` the slots' RNG streams, ``act [S]`` the slots
    that owe a token. Greedy: accept the longest prefix where the target's
    argmax equals the proposal, then take the target's own next token.
    Sampled: accept ``d_i`` with probability ``min(1, p(d_i)/q(d_i))``, on
    the first rejection resample from ``norm(max(p - q, 0))``, after full
    acceptance sample the bonus from ``p``. Returns ``(count [S], corr [S],
    block [S, G + 2], keys)``: tokens emitted (accepted + 1; 0 for a frozen
    slot), the last of them, and ``[count, e_1..e_{G+1}]``."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    with scope("spec.accept"):
        if greedy:
            t = jnp.argmax(logits, axis=-1).astype(i32)    # [S, G+1]
            accept = t[:, :gamma] == d                     # [S, G]
            a = jnp.sum(jnp.cumprod(accept.astype(i32), axis=1), axis=1)
            corr = jnp.take_along_axis(t, a[:, None], axis=1)[:, 0]
        else:
            p = jax.nn.softmax(sample_filtered(logits), axis=-1)
            p_d = jnp.take_along_axis(
                p[:, :gamma], d[..., None], axis=-1)[..., 0]
            q_d = jnp.take_along_axis(q, d[..., None], axis=-1)[..., 0]

            def consume(key):
                key, su = jax.random.split(key)
                u = jax.random.uniform(su, (gamma,))
                key, sc = jax.random.split(key)
                return key, u, sc

            keys, us, subs = jax.vmap(consume)(keys)
            # u < min(1, p/q)  <=>  u*q < p  (q=0 proposals never drawn)
            accept = us * q_d < p_d
            a = jnp.sum(jnp.cumprod(accept.astype(i32), axis=1), axis=1)
            p_a = jnp.take_along_axis(
                p, a[:, None, None], axis=1)[:, 0]         # [S, V]
            q_pad = jnp.concatenate(
                [q, jnp.zeros_like(q[:, :1])], axis=1)
            q_a = jnp.take_along_axis(
                q_pad, a[:, None, None], axis=1)[:, 0]
            res = jnp.maximum(p_a - q_a, 0.0)
            has_res = jnp.sum(res, axis=-1, keepdims=True) > 0
            res = jnp.where(has_res, res, p_a)
            corr = jax.vmap(
                lambda s_, r: jax.random.categorical(
                    s_, jnp.log(jnp.maximum(r, 1e-38))))(subs, res)
            corr = corr.astype(i32)

        count = jnp.where(act, a + 1, 0).astype(i32)
        idx = jnp.arange(gamma + 1)[None, :]
        d_pad = jnp.concatenate(
            [d, jnp.zeros_like(d[:, :1])], axis=1)         # [S, G+1]
        emit = jnp.where(idx < a[:, None], d_pad,
                         jnp.where(idx == a[:, None], corr[:, None], 0))
        block = jnp.concatenate([count[:, None], emit], axis=1)
    return count, corr, block, keys


def _advance_rounds(loop, act, count, corr, keys, **more):
    """A round's effect on the loop state: a slot that owed a token takes
    the round's last token, moves its cursor on by ``count`` and owes as
    many fewer (floored at zero: the host truncates the last round's
    tokens by its own bookkeeping); ``more``: further per-slot values a
    live slot takes (a self-draft's next proposal)."""
    import jax.numpy as jnp

    with scope("spec.accept"):
        new = {"cursors": jnp.where(act, loop["cursors"] + count,
                                    loop["cursors"]),
               "tok": jnp.where(act, corr, loop["tok"]),
               "remaining": jnp.where(
                   act, jnp.maximum(loop["remaining"] - count, 0),
                   loop["remaining"]),
               "keys": keys}
        new.update({k: jnp.where(act, v, loop[k]) for k, v in more.items()})
    return new


@traced
def _serve_mtp_impl(model, sample_filtered, greedy, k_rounds, params, kv,
                    loop, *, pool_kernel=None):
    """K speculative rounds drafted from the model's own
    multi-token-prediction module (``TransformerLM(mtp=)``; DeepSeek-V3,
    arXiv:2412.19437 section 2.2), as ONE program. A slot carries ``tok`` at
    cursor ``c`` and ``draft``, the module's proposal for ``c + 1``. Per
    round and live slot:

    1. **verify** — the target over ``[tok, draft]`` at ``[c, c + 1]``
       (``_serve_verify_impl``: latent rows written at both), logits and
       hidden states for both.
    2. **accept** — ``_accept_round`` with one proposal (the rule of
       speculative sampling; the proposal was an argmax, so ``q`` is
       one-hot): on acceptance
       the round emits the draft and the target's token after it, cursor
       ``c + 2``; else the target's own token, cursor ``c + 1``, and row
       ``c + 1`` stays beyond the cursor, masked until overwritten.
    3. **draft** — the module over the positions just made permanent (two;
       the second holds no token after a rejection) with the emitted tokens'
       embeddings beside the verify's final-normed hidden states, its latent
       rows written at those positions of its own layer (the pool's last);
       its argmax at the last permanent position is the next round's draft.

    No second pool and no second cursor: the module reads position i of the
    target's hidden states and token i + 1, so its rows sit at the target's
    positions, and nothing of a round but the draft goes to the next.
    Returns ``(blocks, loop, pool, routing)``: ``blocks [K, S, 4]``, per
    round and slot ``[count, e_1, e_2, draft verified]``; ``routing`` (None
    without routed experts) ``[K, L + 1, ...]``, each round's
    ``_stack_routing`` over the S x 2 candidates, the layers' and then the
    module's (whose row at a position is the token one further on)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    i32 = jnp.int32
    module = len(model.layers_of("mla"))    # the module's latent layer

    def round_body(carry, _):
        kv, loop = carry
        act = loop["remaining"] > 0
        d = loop["draft"]
        vtoks = jnp.stack([loop["tok"], d], axis=1)         # [S, 2]
        vpos = loop["cursors"][:, None] + jnp.arange(2)[None, :]
        moe_info: list = []
        logits, kv, h = _serve_verify_impl(
            model, params, kv, vtoks, vpos, act, pool_kernel=pool_kernel,
            moe_info=moe_info, hidden=True)
        q = None if greedy else jax.nn.one_hot(d, logits.shape[-1])[:, None]
        count, corr, block, keys = _accept_round(
            act, logits, d[:, None], q, loop["keys"], 1, greedy,
            sample_filtered)
        # ---- draft: the module at the positions that are now permanent
        held = jnp.arange(2)[None, :] < count[:, None]      # [S, 2]
        hm, _, _ = model.mtp_block(
            params, model.mtp_input(params, model._norm(h, params["ln_f"]),
                                    block[:, 1:]),
            positions=vpos, live=held, moe_info=moe_info,
            attention=_latent_attention(model, kv, vpos, live=act)(
                module, params["mtp"]["block"]["mla"]))
        last = jnp.take_along_axis(
            hm, jnp.maximum(count - 1, 0)[:, None, None], axis=1)[:, 0]
        nxt = jnp.argmax(model.mtp_head(params, last), axis=-1).astype(i32)
        loop = _advance_rounds(loop, act, count, corr, keys, draft=nxt)
        # None is an empty tree: a scan stacks nothing for it
        routing = _stack_routing(moe_info) if moe_info else None
        return (kv, loop), (jnp.concatenate([block, d[:, None]], axis=1),
                            routing)

    # no loop round a single round
    (kv, loop), (blocks, routing) = lax.scan(
        round_body, (kv, loop), None, length=k_rounds, unroll=k_rounds == 1)
    return blocks, loop, kv, routing


def seed_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s words, made on the host and handed to a
    program with its other arguments, where the eager call launches a program
    of its own: threefry's key is the seed as 64 bits, high word first, and
    without x64 ``PRNGKey`` keeps a Python int's low word alone. Any other
    generator's key is jax's to make."""
    import jax

    if jax.config.jax_default_prng_impl != "threefry2x32":
        return np.asarray(jax.random.PRNGKey(seed))
    seed = int(np.int64(seed))
    high = seed >> 32 if jax.config.jax_enable_x64 else 0
    return np.array([high, seed], np.int64).astype(np.uint32)


def _record(extra):
    """What a serving program returned beside tokens, keys and pool (and a
    prefill block's carry), as the
    engine hands it on: None (a dense model), the routing array
    (``_stack_routing``), or ``(routing, selection)`` for a model with
    learned sparse attention (``_stack_selection``)."""
    return None if not extra else extra[0] if len(extra) == 1 else tuple(
        extra)


class DecodeEngine:
    """Owns the slot pool + the per-signature program cache.

    ``temperature``/``top_k`` are server-level (baked into the compiled
    programs — a per-request sampling config would be a program
    signature per config, exactly the recompile hazard the server
    exists to avoid); per-request randomness rides in per-slot keys.

    A model with a multi-token-prediction module (``TransformerLM(mtp=)``)
    decodes in speculative rounds drafted from that module (``spec``); any
    other in plain steps.
    """

    # proposals a round verifies, and so the rows a request needs free past
    # its last position: the module proposes one
    spec_tokens = 1

    def __init__(self, model, slots: int, *,
                 max_len: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 kv_dtype: Optional[str] = None, mesh=None,
                 ring: bool = True):
        if temperature < 0.0:
            raise ValueError(f"temperature={temperature} must be >= 0")
        if top_k is not None and not 1 <= top_k <= model.vocab_size:
            raise ValueError(
                f"top_k={top_k} must be in [1, vocab={model.vocab_size}]")
        # a cold replica replays its program set from the persistent
        # compilation cache instead of paying XLA again
        ensure_compile_cache()
        model._ensure_init()
        self.model = model
        # ``mesh=`` serves tensor-parallel: the model's sharding registry
        # (the SAME Megatron specs training uses) places the params over
        # ``model`` and the slot pool shards its head axis to match —
        # decode/prefill programs are partitioned by GSPMD from the input
        # shardings, so a model bigger than one chip's HBM serves on a
        # TP slice with token-identical greedy streams.
        self.mesh = mesh
        self.registry = None
        if mesh is not None:
            from deeplearning4j_tpu.parallel.sharding_registry import (
                ShardingRegistry)

            self.registry = ShardingRegistry.for_transformer(model, mesh)
            model.params = self.registry.place(model.params)
        # ``ring=False``: the window layers of a model with
        # ``attn['windows']`` keep T_max rows like the others
        self.cache = SlotKVCache(model, slots, max_len, kv_dtype,
                                 registry=self.registry, ring=ring)
        self.slots = self.cache.slots
        self.max_len = self.cache.max_len
        self.kv_dtype = self.cache.kv_dtype
        self.temperature = float(temperature)
        self.top_k = top_k
        self.buckets = tuple(b for b in (buckets or DEFAULT_PROMPT_BUCKETS)
                             if b <= self.max_len) or (self.max_len,)
        pool = (model.dsa or {}).get("pool", 1)
        if any(n % pool for n in self.buckets + (self.max_len,)):
            raise ValueError(
                f"pooled index keys (dsa['pool']={pool}): max_len="
                f"{self.max_len} and every prompt bucket {self.buckets} "
                "hold whole pools")
        self._sample_row = _row_sampler(self.temperature, top_k)
        self._programs: Dict[tuple, object] = {}
        self.program_builds = 0
        self._prefill_carry: Dict[int, list] = {}   # prefill_blocks
        # {slot: the first round's draft} between a model with a module's
        # prefill and its admit_slot
        self._first_draft: Dict[int, object] = {}

        if model.mtp and (model.kda or model.gdn or model.ret or model.dsa):
            raise ValueError(
                "speculative decoding is not written for a model with 'kda', "
                "'gdn' or 'ret' layers or an indexer: a rejected draft token would "
                "have to be taken back out of the recurrent state, which "
                "keeps no history to rewind to, and the verify forward knows "
                "no indexer's keys: it neither writes them nor selects")
        if model.mtp and set(model.mixers) != {"mla"}:
            raise NotImplementedError(
                "speculative rounds drafted from a multi-token-prediction "
                "module are written for a stack of 'mla' layers (prefill in "
                f"blocks); this model's are {model.mixers}")

    @property
    def spec(self) -> bool:
        """True when a decode dispatch is a speculative round: the model
        drafts from its own multi-token-prediction module."""
        return bool(self.model.mtp)

    @property
    def block_prefill(self) -> bool:
        """True when a prompt is prefilled in blocks (``prefill_blocks``):
        learned sparse attention, or a model that drafts from its own
        module."""
        return bool(self.model.dsa or self.model.mtp)

    # ------------------------------------------------------------------
    def _program(self, sig: tuple, factory):
        """One jitted program per signature for the engine's lifetime —
        the build count IS the compile count (fixed shapes per
        signature), mirrored into the registry so the bench and the
        soak test can assert flatness after warmup."""
        fn = self._programs.get(sig)
        if fn is None:
            from deeplearning4j_tpu.monitor import record_counter

            fn = self._programs[sig] = factory()
            self.program_builds += 1
            record_counter("serve_program_builds_total", kind=sig[0])
        return fn

    def compile_counts(self) -> dict:
        """``{decode, prefill_buckets, total}`` — the warmup-flatness
        evidence serving artifacts embed (``decode`` counts the
        decode-family program: the plain step or the round)."""
        pre = sorted(s[1] for s in self._programs
                     if s[0].startswith("prefill"))
        return {"decode": sum(1 for s in self._programs
                              if s[0].startswith("decode")),
                "prefill_buckets": pre,
                "total": self.program_builds}

    def slot_state(self, slot: int) -> Tuple[int, int, np.ndarray]:
        """Host readback of one slot's ``(cursor, last token, key)`` —
        sanctioned ONLY at migration boundaries (graceful drain exports a
        mid-stream slot once per request, like the prefill/decode
        handoff's export), never inside the decode loop where the state
        advances on device. It is the state after every DISPATCHED step:
        a server reads its unread token block first
        (``DecodeServer.flush``)."""
        import jax

        loop = jax.device_get(self.cache.loop)
        return (int(loop["cursors"][slot]), int(loop["tok"][slot]),
                loop["keys"][slot])

    # ------------------------------------------------------------------
    def prompt_bucket(self, n: int) -> int:
        return prompt_bucket(n, self.buckets, max_len=self.max_len)

    def prefill_blocks(self, prompt, slot: int, key):
        """``prefill`` for a model with learned sparse attention, one block
        a ``next``: a generator that runs the rung's block program
        (``_serve_prefill_block_impl``) once for each block the prompt has,
        pool and carry donated from one to the next, and yields None after
        every block but the last and ``prefill``'s triple after that one.
        Between two blocks the caller may dispatch decode steps
        (``DecodeServer`` does: one block a scheduler step): the slot is
        frozen meanwhile with
        its cursor at ``prompt_len``, so the row a frozen slot writes in
        every step lands where no query of the prompt reads and where the
        slot's own first decode step writes before it reads. The carry's
        buffers come from the rung's free list and go back to it when the
        generator ends or is closed (a request that left mid-prefill)."""
        import jax
        import jax.numpy as jnp

        model, cache = self.model, self.cache
        bucket, padded, plen = self._padded(prompt)

        def build():
            fn = functools.partial(_serve_prefill_block_impl, model,
                                   self._sample_row)
            return jax.jit(fn, donate_argnums=(1, 2))

        run = self._program(("prefill", bucket), build)
        free = self._prefill_carry.setdefault(bucket, [])
        carry = free.pop() if free else {
            name: jnp.full(shape, fill, jnp.dtype(dt))
            for name, (shape, dt, fill) in prefill_carry_layout(
                model, bucket).items()}
        self.admit_slot(slot, 0, plen, 0, key)
        # one transfer for all the blocks, and no program of its own
        tokens = jax.device_put(padded)
        plen_, slot_ = np.int32(plen), np.int32(slot)
        last = prefill_block_count(plen, bucket) - 1
        try:
            for i in range(last + 1):
                tok, new_key, state, carry, *record = run(
                    model.params, cache.state, carry, tokens, plen_, slot_,
                    key, np.int32(i))
                if model.mtp:
                    self._first_draft[slot] = record.pop()
                cache.install(state)
                yield (tok, new_key, _record(record)) if i == last else None
        finally:
            free.append(carry)

    def prefill(self, prompt, slot: int, key):
        """One prompt ([t] int) into ``slot``'s pool rows: bucket-pad, run
        the prefill program. Returns ``(first_token, new_key, routing)``
        (device values; ``routing`` is ``_stack_routing``'s array, None
        for a dense model, and ``(routing, selection)`` for a model with
        learned sparse attention: ``_record``). The slot decodes once
        ``admit_slot`` has written its loop state."""
        import jax

        if self.block_prefill:      # every block, back to back
            *_, out = self.prefill_blocks(prompt, slot, key)
            return out
        bucket, padded, plen = self._padded(prompt)

        def build():
            fn = functools.partial(_serve_prefill_impl, self.model,
                                   self._sample_row, False)
            return jax.jit(fn, donate_argnums=(1,))

        run = self._program(("prefill", bucket), build)
        tok, key, state, *record = run(
            self.model.params, self.cache.state, padded, np.int32(plen),
            np.int32(slot), key)
        self.cache.install(state)
        return tok, key, _record(record)

    def admit(self, prompt, slot: int, max_new_tokens: int, seed: int):
        """The server's admission of one prompt ([t] int) into ``slot``:
        ONE program (``_serve_admit_impl``, one compile a rung) that
        prefills the slot's pool rows, samples the request's first token
        under the key of ``seed`` and writes the slot's loop state, so the
        slot decodes from the next dispatch on with nothing more from the
        host. Everything it is handed from the host is NumPy, transferred
        with the call: the padded prompt and one int32 vector. Returns
        ``(first_token, routing)`` (device values, ``routing`` as
        ``prefill``'s); nothing here waits for them."""
        import jax

        if self.block_prefill:
            raise ValueError(
                "a model prefilled in blocks is admitted a block a step "
                "(prefill_blocks, admit_slot)")
        bucket, padded, plen = self._padded(prompt)
        if ("slot_admit",) not in self._programs:
            # a slot is released (a deadline, a cancel) only after an
            # admission: the first one of an engine runs the release
            # program, so that no later moment meets it uncompiled
            self.release_slot(slot)

        def build():
            fn = functools.partial(_serve_admit_impl, self.model,
                                   self._sample_row)
            return jax.jit(fn, donate_argnums=(1,))

        run = self._program(("prefill", bucket, "admit"), build)
        # the seed's low 32 bits, as PRNGKey takes a Python int without x64
        at = np.array([slot, plen, max_new_tokens - 1, seed],
                      np.int64).astype(np.int32)
        tok, self.cache.loop, state, *record = run(
            self.model.params, self.cache.state, self.cache.loop, padded, at)
        self.cache.install(state)
        return tok, _record(record)

    def _padded(self, prompt):
        """``(rung, the prompt padded to it as [1, P] int32 on the host,
        its length)``."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be [t] (got {prompt.shape})")
        bucket = self.prompt_bucket(int(prompt.shape[0]))
        padded, plen = pad_prompt(prompt, bucket)
        return bucket, padded[None], plen

    def admit_slot(self, slot: int, tok, cursor: int, remaining: int,
                   key) -> None:
        """A request enters ``slot``'s loop state (one program): ``tok``
        its last emitted token (a device scalar straight from the prefill
        program, or a hand-off's int), ``cursor`` where that token's K/V
        will land, ``remaining`` the tokens it is still owed, ``key`` its
        RNG stream."""
        import jax

        run = self._program(("slot_admit",), lambda: jax.jit(slot_admit))
        self.cache.loop = run(
            self.cache.loop, np.asarray([slot, cursor, remaining], np.int32),
            tok if isinstance(tok, jax.Array) else np.int32(tok), key,
            # one signature: always a draft where the loop state has one
            self._first_draft.pop(slot, np.int32(0)) if self.model.mtp
            else None)

    def release_slot(self, slot: int) -> None:
        """The request in ``slot`` leaves before its last token: the
        slot's loop state with nothing owed, so the next decode step
        freezes it (the admission program again: no program the first
        request did not already run)."""
        keys = self.cache.loop["keys"]
        self.admit_slot(slot, 0, 0, 0, np.zeros(keys.shape[1:], keys.dtype))

    def _decode_jit(self, impl, *bound):
        """The jitted decode-family program ``impl`` with its static
        leading arguments bound and its pool argument donated. A pool
        sharded over a mesh keeps the XLA read, and a recurrent state over
        one the XLA step: GSPMD would gather the whole pool onto every
        chip for a kernel's custom call."""
        import jax

        kw = {} if self.mesh is None else {"pool_kernel": False}
        return jax.jit(functools.partial(impl, *bound, **kw),
                       donate_argnums=(1,))

    def decode(self):
        """One batched step (the PR-10 step) from the
        loop state on the device to the loop state on the device: no
        argument comes from the host. Returns ``(tokens [S], routing)``
        (device; ``routing`` as ``prefill``'s): a live slot's
        token is the one it just sampled, a frozen slot's its last."""
        def build():
            return self._decode_jit(
                _serve_decode_loop_impl, self.model, self._sample_row)

        run = self._program(("decode", self.slots), build)
        self.cache.loop, state, *record = run(
            self.model.params, self.cache.state, self.cache.loop)
        self.cache.install(state)
        return self.cache.loop["tok"], _record(record)

    def decode_spec(self):
        """One speculative round drafted from the model's own module
        (``_serve_mtp_impl``, called with one round a dispatch): returns
        ``(blocks [1, S, 4], routing)`` (device; per slot ``[count, e_1,
        e_2, draft verified]``); pool and loop state advance in place."""
        greedy = self.temperature == 0.0

        def build():
            return self._decode_jit(
                _serve_mtp_impl, self.model,
                None if greedy else _filtered_logits_fn(
                    self.temperature, self.top_k), greedy, 1)

        run = self._program(("decode_spec", self.slots), build)
        blocks, self.cache.loop, state, routing = run(
            self.model.params, self.cache.state, self.cache.loop)
        self.cache.install(state)
        return blocks, routing
