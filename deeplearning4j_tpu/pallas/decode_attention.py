"""Decode attention that reads the serving KV pool where it lies.

The decode-family programs (``serving/engine.py``) keep ONE
``[L, S, T_max, Hkv, Dh]`` K/V pool and update it in place. XLA:TPU will
not hand a convolution a *view* of one layer of that pool: every
``pool[layer]`` feeding the attention dots is first copied out as a
``[S, T_max, Hkv, Dh]`` slab (a static slice, a dynamic slice and a
read-only pool all compile to the same copy). This kernel is the read
that needs no slab, and what it moves follows the keys that are attended,
not the size of the pool: its work list is the key blocks of the slots
that hold a request (``live``), each slot's own ``lo..hi`` — the blocks
its mask can admit, from its sliding window's start to its cursor
(``key_block_span``). A slot that holds no request — finished and not yet
reassigned, or never used — contributes nothing: none of its blocks is
fetched or multiplied, whatever its frozen cursor says, and its output
rows are zeros. Blocks past a live slot's cursor, or before its window,
are not in the list either.

There is no grid. One invocation a layer walks the flat list of ``(slot,
block)`` items in a loop whose trip count is a scalar operand: K and V
stay in HBM (``pl.ANY``) and each item's two blocks are copied into one
of two VMEM buffers (``pltpu.make_async_copy``), the next item's copy in
flight — across slot boundaries too — while this one is multiplied. The
online-softmax state restarts at a slot's first block and the slot's
rows are written at its last. The list itself (``_work_list``: a
cumulative sum and one comparison over ``[S x blocks, S]``) is a few
small XLA ops a step, the same for every layer.

The pool is passed as ``[L, S, T_max * Hkv, Dh]``: position-major,
kv-head-minor rows, which is the pool's own byte order (a free reshape),
with ``Dh`` on the lanes. Row ``r`` is position ``r // Hkv`` of kv head
``r % Hkv``; a query head sees the rows of its own kv head only, so the
grouped attention becomes one masked ``[Q*H, rows]`` product per block —
``Hkv`` times the MXU work of the per-head form, on a kernel that waits
for memory. The queries of all slots (``[S, Q*H, Dh]``) and the output
sit in VMEM whole.

A block is 512 KiB of K (``pool_block_rows``: 2,048 rows in bf16 — 1,024
positions at 2 kv heads, 128 at 16), chosen on the chip (TPU v5e, PR 28):
with no grid step to pay for, a 256 KiB block over-fetches less but costs
more items (0.54 against 0.49 ms for 20 live slots of 32 at 16 kv heads),
a 1 MiB block fetches more than short contexts hold (0.073 against 0.058
ms for 4 live slots of 64 at 2 kv heads).

A **ring** (``ring=True``; ``serving/kv_cache.py``: the rows a slot keeps
for a layer with a sliding window, ``R`` positions instead of ``T_max``) is
read by the same walk: position ``t`` lies at row ``t mod R``, so at a query's
position ``c`` row ``r`` holds position ``c - ((c - r) mod R)``, which the
mask admits when it is not negative (the row has been written) and inside
the window. A slot's blocks are the ring's first ``min(c + 1, R)`` rows
whatever its length: ``key_block_span`` with no window over a pool of ``R``
positions.

``interpret=True`` runs the same kernel through the Pallas interpreter
(CPU tests).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.pallas.flash_attention import _LANES, MASK_VALUE
from deeplearning4j_tpu.scopes import scope

__all__ = ["pool_decode_attention", "pool_block_rows", "key_block_span"]

_BLOCK_BYTES = 1 << 19   # one K (or V) block in VMEM; x2 arrays x2 buffers


def pool_block_rows(pool_shape, dtype) -> Optional[int]:
    """Rows (of ``T_max * Hkv``) per key block for a pool of this shape
    and store dtype, or ``None`` where the kernel does not apply: the head
    dimension must fill whole lane tiles and the rows must split into
    whole blocks."""
    _, _, t_max, hkv, dh = pool_shape
    if dh % _LANES:
        return None
    rows = t_max * hkv
    block = min(rows, _BLOCK_BYTES // (dh * jnp.dtype(dtype).itemsize))
    if block % 16 or rows % block:
        return None
    return block


def key_block_span(newest, oldest, *, block, hkv, window, t_max):
    """``(lo, hi)``: the first and last key block (``block`` rows of
    ``T_max * Hkv``) a slot's queries can admit, from the newest and the
    oldest of their positions — for a decode step both are the slot's
    cursor. Positions are clipped into the pool (a frozen slot may sit
    past its end); a sliding window drops the blocks before ``oldest -
    window + 1``. Plain arithmetic over numpy or jax integers: the
    kernel's wrapper computes its work list with it and the server's
    host counters (``kv_blocks``) count with it."""
    xp = jnp if isinstance(newest, jax.Array) else np
    newest = xp.clip(newest, 0, t_max - 1)
    oldest = (xp.zeros_like(newest) if window is None
              else xp.clip(oldest - (window - 1), 0, newest))
    return oldest * hkv // block, (newest * hkv + hkv - 1) // block


def _work_list(positions, live, *, block, hkv, window, t_max):
    """The kernel's scalar operands from ``positions [S, Q]`` and ``live
    [S]`` (or ``None``): ``(n, slot_of, lo, hi, first)``. Work item ``w
    < n[0]`` is key block ``lo[s] + w - first[s]`` of slot ``s =
    slot_of[w]``: every live slot's blocks ``lo[s]..hi[s]``, slot after
    slot; a slot that is not live has none."""
    lo, hi = key_block_span(
        jnp.max(positions, axis=1), jnp.min(positions, axis=1),
        block=block, hkv=hkv, window=window, t_max=t_max)
    count = hi - lo + 1
    if live is not None:
        count = jnp.where(live, count, 0)
    ends = jnp.cumsum(count)
    items = positions.shape[0] * (t_max * hkv // block)
    slot_of = jnp.sum(jnp.arange(items)[:, None] >= ends[None, :], axis=1,
                      dtype=jnp.int32)
    return ends[-1:], slot_of, lo, hi, ends - count


def _kernel(n_ref, slot_ref, lo_ref, hi_ref, first_ref, pos_ref,
            q_ref, qhead_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem, acc_ref, m_ref, l_ref, *,
            layer, scale, block, hkv, window, heads, queries, ring):
    # dead slots, and rows the work list never reaches, read zeros
    o_ref[...] = jnp.zeros_like(o_ref)
    n = n_ref[0]

    def block_of(w):
        s = slot_ref[w]
        return s, lo_ref[s] + (w - first_ref[s])

    def copies(s, blk, buf):
        rows = pl.ds(pl.multiple_of(blk * block, block), block)
        return [pltpu.make_async_copy(hbm.at[layer, s, rows], vm.at[buf],
                                      sem.at[i, buf])
                for i, (hbm, vm) in enumerate(((k_hbm, k_buf),
                                               (v_hbm, v_buf)))]

    @pl.when(n > 0)
    def _():
        for c in copies(*block_of(0), 0):
            c.start()

    def item(w, carry):
        buf = w & 1

        @pl.when(w + 1 < n)         # the next block flies while this one
        def _():                    # is multiplied
            for c in copies(*block_of(w + 1), 1 - buf):
                c.start()

        s, blk = block_of(w)
        for c in copies(s, blk, buf):
            c.wait()

        @pl.when(blk == lo_ref[s])
        def _():
            m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[s]                                        # [M, D]
        k = k_buf[buf].astype(q.dtype)                      # [block, D]
        v = v_buf[buf].astype(q.dtype)
        logits = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [M, block]
        row = blk * block + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        if hkv & (hkv - 1):
            t, head = row // hkv, row % hkv
        else:           # a power of two: shifts, not vector division
            t, head = row >> (hkv.bit_length() - 1), row & (hkv - 1)
        # per product row: its query's position (-1 on pad rows: nothing
        # is admitted); rows are query-major, head-minor
        mrow = lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0)
        qpos = jnp.full_like(mrow, -1)
        for i in range(queries):
            qpos = jnp.where((mrow >= i * heads) & (mrow < (i + 1) * heads),
                             pos_ref[s * queries + i], qpos)
        if ring is not None:
            # the position this ring row holds at the query's: negative
            # where it has not been written (or the query is a pad row)
            back = qpos - t
            t = qpos - (back & (ring - 1) if ring & (ring - 1) == 0
                        else jnp.remainder(back, ring))
            keep = (head == qhead_ref[...]) & (t >= 0)
        else:
            keep = (head == qhead_ref[...]) & (t <= qpos)
        if window is not None:
            keep &= t > qpos - window
        logits = jnp.where(keep, logits, MASK_VALUE)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row with no admitted key yet has m_new == MASK_VALUE, where
        # exp(logits - m_new) would be 1: zero by the mask, not by exp
        p = jnp.where(keep, jnp.exp(logits - m_new[:, :1]), 0.0)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(blk == hi_ref[s])
        def _():
            l = l_ref[...][:, :1]
            o_ref[s] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
                o_ref.dtype)

        return carry

    lax.fori_loop(0, n, item, 0)


def pool_decode_attention(q, pool_k, pool_v, layer: int, positions, *,
                          window: Optional[int] = None,
                          block_rows: Optional[int] = None,
                          interpret: bool = False, live=None,
                          hkv: Optional[int] = None, ring: bool = False,
                          name: Optional[str] = None):
    """Attention of ``q [S, Q, H, Dh]`` at absolute ``positions [S, Q]``
    against layer ``layer`` of the ``[L, S, T_max, Hkv, Dh]`` pools (or
    their rows ``[L, S, T_max Hkv, Dh]`` with ``hkv`` kv heads, the shape a
    pool of heads wider than a lane tile is stored in:
    ``serving/kv_cache.pool_shape``): query
    ``(s, i)`` attends keys ``t <= positions[s, i]`` of slot ``s`` (and
    ``t > positions[s, i] - window``). Returns ``[S, Q, H, Dh]`` in
    ``q.dtype`` — the mathematics of ``grouped_query_attention`` over
    ``pool[layer]`` under the same mask, as a blockwise online softmax.
    The pools may store another float dtype; blocks are cast to
    ``q.dtype`` in VMEM. ``live [S]`` (bool) names the slots that hold a
    request: nothing of the others is fetched or multiplied and their
    rows are zeros. ``None``: every slot is live.

    ``ring``: the pools are rings of ``R`` = their third axis' positions
    (the module's docstring): one query a slot, which attends the positions
    ``> positions[s, 0] - window`` that the ring's rows hold. ``name``: a
    scope of ``scopes.py`` to open round the ``pallas_call``, which names
    the kernel's instruction in a device trace (a window layer's read is
    ``attn.window``; with none the call keeps its own name)."""
    s_, nq, h, dh = q.shape
    if pool_k.ndim == 4:
        n_layers, t_max = pool_k.shape[0], pool_k.shape[2] // hkv
    else:
        n_layers, _, t_max, hkv, _ = pool_k.shape
    block = block_rows or pool_block_rows(
        (n_layers, s_, t_max, hkv, dh), pool_k.dtype)
    if block is None or (t_max * hkv) % block or h % hkv:
        raise ValueError(
            f"pool {pool_k.shape} ({pool_k.dtype}) with {h} query heads "
            "does not fit the decode kernel's blocks")
    if ring and nq != 1:
        raise NotImplementedError(
            "a ring of rows is read by one query a slot (a decode step): "
            f"{nq} queries a slot would each need the ring as of their own "
            "position")
    m = nq * h
    m_pad = -(-m // 16) * 16            # whole bf16 sublane tiles
    # the operands' layout and the work list are XLA ops of the pool's side
    # of the step (``kv.write``); the scope closes before the kernel, which
    # keeps the name the trace's readers know (``scopes.py``)
    with scope("kv.write"):
        positions = positions.astype(jnp.int32)
        qf = jnp.pad(q.reshape(s_, m, dh),
                     ((0, 0), (0, m_pad - m), (0, 0)))
        # per product row: the kv head its query head reads
        qhead = jnp.pad(jnp.tile(
            jnp.arange(h, dtype=jnp.int32) // (h // hkv), nq),
            (0, m_pad - m))[:, None]
        operands = (*_work_list(positions, live, block=block, hkv=hkv,
                                window=None if ring else window,
                                t_max=t_max),
                    positions.reshape(-1), qf, qhead,
                    pool_k.reshape(n_layers, s_, t_max * hkv, dh),
                    pool_v.reshape(n_layers, s_, t_max * hkv, dh))

    kernel = functools.partial(
        _kernel, layer=layer, scale=float(1.0 / (dh ** 0.5)), block=block,
        hkv=hkv, window=window, heads=h, queries=nq,
        ring=t_max if ring else None)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(),
            in_specs=[vmem, vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, block, dh), pool_k.dtype),
                pltpu.VMEM((2, block, dh), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((m_pad, dh), jnp.float32),
                pltpu.VMEM((m_pad, _LANES), jnp.float32),
                pltpu.VMEM((m_pad, _LANES), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((s_, m_pad, dh), q.dtype),
        interpret=interpret,
    )
    with scope(name) if name else contextlib.nullcontext():
        out = call(*operands)
    with scope("kv.write"):
        return out[:, :m].reshape(s_, nq, h, dh)
