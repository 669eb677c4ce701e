"""Routed experts that fetch only the experts a step's rows reached.

``models/routed_experts._dense_experts`` runs every expert held on every
row and gives unchosen results weight zero: one pass over all the stacked
matrices, which is the roofline of what it reads. When a step has few
rows for the experts it holds (a decode step over one chip's share of a
wide router: 64 slots x 8 choices over 512 experts is one pair an expert
held) most of those matrices are multiplied into zeros. This kernel is
the same sum with a work list: the ids of the held experts that received
at least one live (token, expert) pair (``work_list``: ``load > 0``,
packed to the front, and their count). Per listed expert it fetches
``w_gate[e]``, ``w_up[e]`` and ``w_down[e]`` once, as stored, rounds each
block to the compute dtype in VMEM, and adds for all N rows

    (silu(x Wgate[e]) * (x Wup[e]) * combine[:, e]) Wdown[e]

(float32 products of compute-dtype operands, the hidden rounded to the
compute dtype as the dense form rounds it) into a float32 ``y [N, D]``.
An expert not in the list is never fetched; an empty list gives zeros.
Only the order of the float32 sum over experts differs from the dense
form's single contraction.

There is no grid. The matrices stay in HBM (``pl.ANY``) and are copied in
row blocks that are contiguous as stored: ``d_block`` rows of ``[D, F]``
for gate and up together (the contraction is summed over blocks in a
float32 scratch), then ``f_block`` rows of ``[F, D]`` for down
(accumulated into the output). Each block lands in one of two buffers of
its kind while the block before it is multiplied, across expert
boundaries too; the loop's trip count is the list's length, a scalar
operand. No compute-dtype copy of any matrix exists in HBM.

On the chip (TPU v5e, PR 30) at Ling's widths — 64 rows, D 2,560, F 768,
64 experts held, 22 reached — the block size does not matter: 0.716–0.723
ms a call from float32 storage (519 MB: 718–725 GB/s) for blocks of 128
rows (393 KiB) up to whole matrices, 0.371–0.374 ms from bf16 storage;
8 / 32 / 64 experts reached take 0.278 / 1.029 / 2.030 ms (679 / 734 /
744 GB/s), none 0.034. ``_BLOCK_BYTES`` is 2 MiB: 14 copies an expert at
these widths and 10 MiB of buffers, well inside VMEM.

``interpret=True`` runs the same kernel through the Pallas interpreter
(CPU tests).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.pallas.flash_attention import _LANES

__all__ = ["reached_experts", "expert_blocks", "work_list"]

_BLOCK_BYTES = 2 << 20   # one block of one matrix in VMEM, as stored
_MAX_ROWS = 256          # x, the hidden and y sit in VMEM whole
_VMEM_BYTES = 64 << 20


def _rows_per_block(rows: int, row_bytes: int) -> Optional[int]:
    """The most rows of a ``[rows, width]`` matrix a block may hold: a
    whole number of lane tiles (the rows are the other product's lanes)
    that divides ``rows``. Where one lane tile of rows is already more than
    ``_BLOCK_BYTES`` (a ``[2048, 6144]`` float32 down projection: 3 MiB),
    that one tile is the block, up to twice ``_BLOCK_BYTES``."""
    best = None
    for block in range(_LANES, rows + 1, _LANES):
        if rows % block == 0 and block * row_bytes <= _BLOCK_BYTES:
            best = block
    if (best is None and rows % _LANES == 0
            and _LANES * row_bytes <= 2 * _BLOCK_BYTES):
        best = _LANES
    return best


def expert_blocks(n: int, d: int, f: int, dtype) -> Optional[Tuple[int, int]]:
    """``(d_block, f_block)`` for ``n`` rows against experts of ``[D, F]``
    / ``[F, D]`` stored as ``dtype``, or ``None`` where the kernel does
    not apply: both widths must be whole lane tiles and the rows must fit
    VMEM whole."""
    if d % _LANES or f % _LANES or n > _MAX_ROWS:
        return None
    size = jnp.dtype(dtype).itemsize
    blocks = (_rows_per_block(d, f * size), _rows_per_block(f, d * size))
    return None if None in blocks else blocks


def work_list(load):
    """``(count [1], ids [held])`` int32 from ``load [held]``: the experts
    that received a pair, lowest id first, then zeros. (Also the live slots
    of ``delta_step.py``, from a bool mask.)"""
    reached = load > 0
    ids = jnp.nonzero(reached, size=load.shape[0], fill_value=0)[0]
    return (jnp.sum(reached, dtype=jnp.int32)[None], ids.astype(jnp.int32))


def _kernel(n_ref, ids_ref, x_ref, combine_ref, gate_hbm, up_hbm, down_hbm,
            o_ref, gu_buf, down_buf, gu_sem, down_sem, gate_acc, up_acc,
            hidden_ref, *, d_block, f_block, limit=None):
    o_ref[...] = jnp.zeros_like(o_ref)
    n = n_ref[0]
    d, f = gate_hbm.shape[1:]
    cdt = x_ref.dtype
    # an expert's blocks in the order they are used: (down?, block)
    stages = ([(False, c) for c in range(d // d_block)]
              + [(True, c) for c in range(f // f_block)])

    def copies(e, stage):
        down, c = stage
        buf = c & 1
        if down:
            return [pltpu.make_async_copy(
                down_hbm.at[e, pl.ds(c * f_block, f_block)],
                down_buf.at[buf], down_sem.at[buf])]
        rows = pl.ds(c * d_block, d_block)
        return [pltpu.make_async_copy(hbm.at[e, rows], gu_buf.at[i, buf],
                                      gu_sem.at[i, buf])
                for i, hbm in enumerate((gate_hbm, up_hbm))]

    @pl.when(n > 0)
    def _():
        for c in copies(ids_ref[0], stages[0]):
            c.start()

    def expert(r, carry):
        e = ids_ref[r]
        for t, stage in enumerate(stages):
            # the next block flies while this one is multiplied
            if t + 1 < len(stages):
                for c in copies(e, stages[t + 1]):
                    c.start()
            else:
                @pl.when(r + 1 < n)
                def _():
                    for c in copies(ids_ref[r + 1], stages[0]):
                        c.start()
            for c in copies(e, stage):
                c.wait()
            down, c = stage
            buf = c & 1
            if down:
                o_ref[...] += lax.dot_general(
                    hidden_ref[:, c * f_block:(c + 1) * f_block],
                    down_buf[buf].astype(cdt), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                continue
            xs = x_ref[:, c * d_block:(c + 1) * d_block]
            for i, acc in enumerate((gate_acc, up_acc)):
                part = lax.dot_general(
                    xs, gu_buf[i, buf].astype(cdt), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if c == 0:
                    acc[...] = part
                else:
                    acc[...] += part
            if (c + 1) * d_block == d:
                lane = lax.broadcasted_iota(jnp.int32, combine_ref.shape, 1)
                weight = jnp.sum(
                    jnp.where(lane == e, combine_ref[...], 0.0), axis=1,
                    keepdims=True)                          # [N, 1]
                if limit is None:
                    hidden_ref[...] = (jax.nn.silu(gate_acc[...])
                                       * up_acc[...] * weight).astype(cdt)
                else:   # ``routed_experts.swiglu``'s clamp
                    hidden_ref[...] = (
                        jax.nn.silu(jnp.minimum(gate_acc[...], limit))
                        * jnp.clip(up_acc[...], -limit, limit)
                        * weight).astype(cdt)
        return carry

    lax.fori_loop(0, n, expert, 0)


def reached_experts(x, combine, load, w_gate, w_up, w_down, *,
                    blocks: Optional[Tuple[int, int]] = None,
                    interpret: bool = False, limit: Optional[float] = None):
    """``sum_e (silu(x Wgate[e]) * (x Wup[e]) * combine[:, e]) Wdown[e]``
    over the experts ``e`` with ``load[e] > 0``: ``x [N, D]`` in the
    compute dtype, ``combine [N, held]`` float32 (a row's weight for each
    expert held, zero where it did not choose it), ``load [held]`` the
    live pairs each expert received (``limit``: the gate clamped to at most
    it and the up projection to within it before the product), ``w_gate`` / ``w_up`` ``[held, D,
    F]`` and ``w_down`` ``[held, F, D]`` in the dtype they are stored in
    (rounded to ``x.dtype`` after the fetch). Returns ``y [N, D]``
    float32. A pair of a row whose expert has ``load`` 0 must carry
    weight zero: it is left out."""
    n, d = x.shape
    held, _, f = w_gate.shape
    blocks = blocks or expert_blocks(n, d, f, w_gate.dtype)
    if blocks is None or d % blocks[0] or f % blocks[1]:
        raise ValueError(
            f"{n} rows against experts {w_gate.shape} ({w_gate.dtype}) do "
            "not fit the reached-experts kernel's blocks")
    d_block, f_block = blocks
    n_pad = -(-n // 16) * 16            # whole bf16 sublane tiles
    x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    combine = jnp.pad(combine.astype(jnp.float32), ((0, n_pad - n), (0, 0)))

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_kernel, d_block=d_block, f_block=f_block,
                          limit=limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(),
            in_specs=[vmem, vmem, hbm, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, 2, d_block, f), w_gate.dtype),
                pltpu.VMEM((2, f_block, d), w_down.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((n_pad, f), jnp.float32),
                pltpu.VMEM((n_pad, f), jnp.float32),
                pltpu.VMEM((n_pad, f), x.dtype),
            ]),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(*work_list(load), x, combine, w_gate, w_up, w_down)
    return out[:n]
