"""Decode attention that reads the serving KV pool where it lies.

The decode-family programs (``serving/engine.py``) keep ONE
``[L, S, T_max, Hkv, Dh]`` K/V pool and update it in place. XLA:TPU will
not hand a convolution a *view* of one layer of that pool: every
``pool[layer]`` feeding the attention dots is first copied out as a
``[S, T_max, Hkv, Dh]`` slab (a static slice, a dynamic slice and a
read-only pool all compile to the same copy). This kernel is the read
that needs no slab: its block index maps address ``(layer, slot, key
block)`` of the pool itself, so the only pool bytes that move are the key
blocks a slot's mask can admit — blocks past the slot's cursor, or before
its sliding window, are neither fetched nor computed.

The pool is passed as ``[L, S, T_max * Hkv, Dh]``: position-major,
kv-head-minor rows, which is the pool's own byte order (a free reshape),
with ``Dh`` on the lanes. Row ``r`` is position ``r // Hkv`` of kv head
``r % Hkv``; a query head sees the rows of its own kv head only, so the
grouped attention becomes one masked ``[Q*H, rows]`` product per block —
``Hkv`` times the MXU work of the per-head form, on a kernel that waits
for memory.

``interpret=True`` runs the same kernel through the Pallas interpreter
(CPU tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.pallas.flash_attention import _LANES, MASK_VALUE

__all__ = ["pool_decode_attention", "pool_block_rows"]

_BLOCK_BYTES = 1 << 20   # one K (or V) block in VMEM; x2 arrays x2 buffers


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


def _kernel(lo_ref, hi_ref, q_ref, qpos_ref, qhead_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, block, hkv, window):
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = lo_ref[s] + j

    @pl.when(blk <= hi_ref[s])
    def _():
        q = q_ref[0]                                        # [M, D]
        k = k_ref[0, 0].astype(q.dtype)                     # [block, D]
        v = v_ref[0, 0].astype(q.dtype)
        logits = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [M, block]
        row = blk * block + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        if hkv & (hkv - 1):
            t, head = row // hkv, row % hkv
        else:           # a power of two: shifts, not vector division
            t, head = row >> (hkv.bit_length() - 1), row & (hkv - 1)
        qpos = qpos_ref[0]                                  # [M, 1]
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

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def pool_decode_attention(q, pool_k, pool_v, layer: int, positions, *,
                          window: Optional[int] = None,
                          block_rows: Optional[int] = None,
                          interpret: bool = False):
    """Attention of ``q [S, Q, H, Dh]`` at absolute ``positions [S, Q]``
    against layer ``layer`` of the ``[L, S, T_max, Hkv, Dh]`` pools: query
    ``(s, i)`` attends keys ``t <= positions[s, i]`` of slot ``s`` (and
    ``t > positions[s, i] - window``). Returns ``[S, Q, H, Dh]`` in
    ``q.dtype`` — the mathematics of ``grouped_query_attention`` over
    ``pool[layer]`` under the same mask, as a blockwise online softmax.
    The pools may store another float dtype; blocks are cast to
    ``q.dtype`` in VMEM."""
    s_, nq, h, dh = q.shape
    n_layers, _, t_max, hkv, _ = pool_k.shape
    block = block_rows or pool_block_rows(pool_k.shape, pool_k.dtype)
    if block is None or (t_max * hkv) % block or h % hkv:
        raise ValueError(
            f"pool {pool_k.shape} ({pool_k.dtype}) with {h} query heads "
            "does not fit the decode kernel's blocks")
    m = nq * h
    m_pad = -(-m // 16) * 16            # whole bf16 sublane tiles
    n_blocks = t_max * hkv // block

    positions = positions.astype(jnp.int32)
    # the key blocks any query of the slot can see: rows of positions
    # (oldest admitted .. newest), clipped into the pool for frozen slots
    newest = jnp.clip(jnp.max(positions, axis=1), 0, t_max - 1)
    oldest = jnp.min(positions, axis=1)
    oldest = (jnp.zeros_like(oldest) if window is None
              else oldest - (window - 1))
    oldest = jnp.clip(oldest, 0, newest)
    lo = oldest * hkv // block
    hi = (newest * hkv + hkv - 1) // block

    qf = jnp.pad(q.reshape(s_, m, dh), ((0, 0), (0, m_pad - m), (0, 0)))
    # per product row: the query's position (-1 on pad rows: nothing is
    # admitted) and the kv head its query head reads
    qpos = jnp.pad(jnp.repeat(positions, h, axis=1),
                   ((0, 0), (0, m_pad - m)), constant_values=-1)[..., None]
    qhead = jnp.pad(jnp.tile(jnp.arange(h, dtype=jnp.int32) // (h // hkv),
                             nq), (0, m_pad - m))[:, None]

    def kv_index(s, j, lo_ref, hi_ref):
        # past the slot's last block the index repeats, so nothing is fetched
        return (layer, s, jnp.minimum(lo_ref[s] + j, hi_ref[s]), 0)

    def row_index(s, j, lo_ref, hi_ref):
        return (s, 0, 0)

    kernel = functools.partial(
        _kernel, scale=float(1.0 / (dh ** 0.5)), block=block, hkv=hkv,
        window=window)
    kv_spec = pl.BlockSpec((1, 1, block, dh), kv_index)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s_, n_blocks),
            in_specs=[
                pl.BlockSpec((1, m_pad, dh), row_index),
                pl.BlockSpec((1, m_pad, 1), row_index),
                pl.BlockSpec((m_pad, 1), lambda s, j, lo_ref, hi_ref: (0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((1, m_pad, dh), row_index),
            scratch_shapes=[
                pltpu.VMEM((m_pad, dh), jnp.float32),
                pltpu.VMEM((m_pad, _LANES), jnp.float32),
                pltpu.VMEM((m_pad, _LANES), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((s_, m_pad, dh), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            # slots are independent; the key blocks carry the softmax state
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lo, hi, qf, qpos, qhead,
      pool_k.reshape(n_layers, s_, t_max * hkv, dh),
      pool_v.reshape(n_layers, s_, t_max * hkv, dh))
    return out[:, :m].reshape(s_, nq, h, dh)
