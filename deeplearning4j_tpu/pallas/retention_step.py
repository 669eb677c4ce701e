"""One position of power retention (degree 2), over the slots that owe a token.

``models/ret.py::ret_step`` is a decode step's recurrence as XLA ops over
every slot of the serving pool:

    S_t = g S + phi(k) v^T;  Z_t = g Z + k k^T;
    o_r = S_t^T phi(q_r) / (q_r^T Z_t q_r + eps)      r: the kv head's queries

on a float32 ``S`` [D, dv] a key/value head (``D`` = 8,704 rows at a head of
128: 4.46 MB, sixteen times a delta-rule head's matrix) and the normaliser's
``Z`` [d, d]. There every slot's ``S`` is read, scaled and written whoever is
live, and ``phi`` of every row is expanded in HBM. This kernel is the same
arithmetic with a work list, in place, as ``pallas/delta_step.py`` is for the
delta rule:

- the list is the slots with ``live`` true, lowest first, and their count
  (``reached_experts.work_list``), scalar-prefetched. The grid is ``(slots,
  kv heads)``: entry ``i`` below the count is slot ``idx[i]``; an entry past
  it does nothing (``pl.when``) and its blocks are the last live entry's
  last, so nothing is fetched for it and nothing is written twice;
- ``S`` and ``Z`` are aliased to the outputs: a live slot's are read once
  and written once, a slot that is not live is neither read nor written and
  keeps its bits. (No slot live at all: the one block of slot 0 the pipeline
  holds is copied through.) ``o`` is written for the live slots only; the
  wrapper puts zeros in the other rows.

A block is one head's whole ``S`` (4.46 MB in, 4.46 MB out, each twice for
the pipeline: 17.8 MB of VMEM), so a head's products never leave the chip
between the update and the read. ``phi`` is never laid out: in the layout
stored (``models/ret.py``) the eight rows of a sublane tile of ``S`` share
``i`` and run over eight consecutive ``j``, so their ``phi(k)`` is ``k_i``
times eight entries of ``k`` down the sublanes. ``k`` and each query are
spread once a head into ``[d, d]`` matrices whose row ``j`` holds entry ``j``
on every lane (a broadcast and one transpose; a second copy times ``sqrt 2``
for the tiles above the diagonal blocks), kept in VMEM scratch; a tile then
costs two multiplies and an add for the update and a multiply and an add a
query for the read, on operands that are plain sublane slices. The loop runs
four ``i`` at a time so that the queries' column tiles are loaded once for
four tiles of ``S``, and the read is summed per ``i`` before it meets
``q_i``: 13 vector operations a tile of 8 x 128, float32 on the VPU, no
product through the MXU (a ``[5, D] x [D, 128]`` product would load each 128
rows of ``S`` as the stationary operand for 5 rows pushed).

On the chip (TPU v5e, PR 47, ``scripts/retention_step_bench.py``: one
layer's state of 32 slots, 50 positions in one loop over a donated state):
0.10 / 0.20 / 0.96 / 2.26 / 3.55 ms at 0 / 1 / 8 / 20 / 32 live slots, 0.108 ms
a live slot (72.4 MB moved: 670 GB/s, what ``delta_step.py`` reaches on blocks
a ninth the size) and 0.10 ms that do not scale: the pipeline's first fetch
and last write-back of a 4.46 MB block and the grid's entries past the list,
``(slots - live) x 8`` of them. 78-80 % of 819 GB/s from 20 live slots up;
``ret_step`` as XLA ops takes 0.89 ms for 4 slots. One block size was tried:
a head is the unit whose products stay on the chip, and the whole-head block
already runs at the bandwidth the smaller kernel's blocks reach. The
operations are not the limit at this size (13 a tile against 8 KiB moved a
tile); a state stored narrower, or folded once a chunk of positions instead
of once a position, is what would move the time (ROADMAP, Reach).

Every process that builds a decode program traces and lowers this kernel
before its compile cache can answer (``delta_step.py``'s docstring): the two
loops keep the kernel's text at one group's, and the layers of a model share
one jitted function (``_retention_step``). The decode program's trace and
lowering read 0.47 s on this sandbox's CPU with the kernel and 0.49 s with
``ret_step`` in its place (PR 47): it adds nothing to ``setup_s``.

``interpret=True`` runs the same kernel through the Pallas interpreter (CPU
tests; the default where no TPU is attached).
"""

from __future__ import annotations

import functools
import importlib
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.pallas.reached_experts import work_list

__all__ = ["retention_step"]

# the module: the package gives its name to the function it re-exports
_flash = importlib.import_module("deeplearning4j_tpu.pallas.flash_attention")

_TILE = 8                   # ``models/ret.TILE``: the layout's tile along j
_GROUP = 4                  # the i a pass of the inner loop takes together
_SQRT2 = math.sqrt(2.0)
_VMEM_BYTES = 48 << 20


def _kernel(n_ref, idx_ref, q_ref, k_ref, v_ref, a_ref, s_ref, z_ref,
            o_ref, s_out, z_out, kb, qb, *, eps):
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when((i == 0) & (j == 0) & (n == 0))
    def _():                    # no list: the one block the pipeline holds
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n)
    def _():
        _head(q_ref, k_ref, v_ref, a_ref, s_ref, z_ref, o_ref, s_out, z_out,
              kb, qb, eps=eps)


def _head(q_ref, k_ref, v_ref, a_ref, s_ref, z_ref, o_ref, s_out, z_out,
          kb, qb, *, eps):
    """One key/value head of one slot: its ``S`` and ``Z`` moved on by one
    position and read by its queries."""
    rep, d = q_ref.shape[2:]
    blocks = d // _TILE
    k, v, a, q = k_ref[0, 0], v_ref[0, 0], a_ref[0, 0], q_ref[0, 0]

    def spread(row):            # [1, d] -> [d, d]: row j holds entry j
        return jnp.broadcast_to(row, (d, d)).T

    # the normaliser, whole: Z_t = g Z + k k^T; q^T Z_t q a query
    kc = spread(k)
    z = a * z_ref[0, 0] + kc * k
    z_out[0, 0] = z
    kb[0], kb[1] = kc, kc * _SQRT2
    den = []
    for r in range(rep):
        qc = spread(q[r:r + 1])
        qb[r, 0], qb[r, 1] = qc, qc * _SQRT2
        den.append(jnp.sum(jnp.sum(qc * q[r:r + 1] * z, axis=0,
                                   keepdims=True), axis=1, keepdims=True))
    tile = (_TILE, d)
    decay = jnp.broadcast_to(a, tile)
    vrow = jnp.broadcast_to(v, tile)
    zero = jnp.zeros(tile, jnp.float32)

    def group(g, out):
        """Four ``i`` of block row ``I``: their tiles of every block column
        ``J >= I``."""
        blk, half = g // 2, g % 2
        cols = blocks - blk
        first = (_TILE * _TILE * (blocks * blk - blk * (blk - 1) // 2)
                 + half * _GROUP * _TILE * cols)
        i0 = _TILE * blk + _GROUP * half
        # phi(k)'s part that a tile's rows share, times v: (k_i v) [8, dv]
        kv = [jnp.broadcast_to(kb[0, pl.ds(i0 + u, 1), :], tile) * vrow
              for u in range(_GROUP)]

        def column(c, acc):
            above = (c > 0).astype(jnp.int32)       # sqrt 2 off the diagonal
            at = pl.ds(pl.multiple_of(_TILE * (blk + c), _TILE), _TILE)
            kcol = kb[above, at, :]
            qcol = [qb[r, above, at, :] for r in range(rep)]
            acc = list(acc)                         # ordered [r][u]
            for u in range(_GROUP):
                rows = pl.ds(pl.multiple_of(
                    first + _TILE * (_GROUP * c + u), _TILE), _TILE)
                s = decay * s_ref[0, 0, rows, :] + kcol * kv[u]
                s_out[0, 0, rows, :] = s
                for r in range(rep):
                    acc[r * _GROUP + u] = acc[r * _GROUP + u] + qcol[r] * s
            return tuple(acc)

        acc = lax.fori_loop(0, cols, column, (zero,) * (rep * _GROUP))
        return tuple(
            out[r] + sum(jnp.broadcast_to(
                qb[r, 0, pl.ds(i0 + u, 1), :], tile) * acc[r * _GROUP + u]
                for u in range(_GROUP))
            for r in range(rep))

    out = lax.fori_loop(0, 2 * blocks, group, (zero,) * rep)
    o_ref[0, 0] = jnp.concatenate(
        [jnp.sum(out[r], axis=0, keepdims=True) / (den[r] + eps)
         for r in range(rep)], axis=0)


def retention_step(q, k, v, lg, s, z, live=None, *, eps: float,
                   interpret: Optional[bool] = None):
    """``ret_step`` for the rows of ``live``: ``q`` [b, H, d], ``k``, ``v``
    [b, Hkv, d] (q and k already times ``d^-1/4``), ``lg`` [b, Hkv] (the
    log-gate), ``s`` [b, Hkv, D, d], ``z`` [b, Hkv, d, d], all float32;
    ``live`` [b] (bool; None: every row). Returns ``(o [b, H, d], s, z)``: a
    row that is not live has ``o`` zero and its state's bits; the state is
    updated in place where the caller donates it. ``interpret``: None =
    where no TPU is attached (``flash_default_interpret``)."""
    if interpret is None:
        interpret = _flash.flash_default_interpret()
    if live is None:
        live = jnp.ones((q.shape[0],), bool)
    return _retention_step(q, k, v, lg, s, z, live, eps=float(eps),
                           interpret=interpret)


# jitted: a model's layers share one trace and one lowered function
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _retention_step(q, k, v, lg, s, z, live, *, eps, interpret):
    b, h, d = q.shape
    hkv, rows = s.shape[1:3]
    rep = h // hkv
    blocks = d // _TILE
    if (d % _TILE or v.shape[-1] != d
            or rows != _TILE * _TILE * blocks * (blocks + 1) // 2):
        raise ValueError(
            f"a state of {rows} rows for heads of {d} (values of "
            f"{v.shape[-1]}): the kernel is written for the layout of "
            "models/ret.py, keys and values of one size")

    def entry(i, j, n_ref, idx_ref):
        # past the list: the last live entry's last block, fetched already
        last = jnp.maximum(n_ref[0] - 1, 0)
        return (idx_ref[jnp.minimum(i, last)],
                jnp.where(i < n_ref[0], j, hkv - 1))

    def block(*shape):
        return pl.BlockSpec((1, 1) + shape, lambda i, j, n, idx: (
            *entry(i, j, n, idx), 0, 0))

    o, s, z = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv),
            in_specs=[block(rep, d), block(1, d), block(1, d), block(1, d),
                      block(rows, d), block(d, d)],
            out_specs=[block(rep, d), block(rows, d), block(d, d)],
            scratch_shapes=[pltpu.VMEM((2, d, d), jnp.float32),
                            pltpu.VMEM((rep, 2, d, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, rep, d), jnp.float32),
                   jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct(z.shape, jnp.float32)],
        # operands 6 and 7 count the two scalar operands: the state
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(*work_list(live), q.reshape(b, hkv, rep, d), k[:, :, None, :],
      v[:, :, None, :],
      jnp.broadcast_to(jnp.exp(lg)[:, :, None, None], (b, hkv, 1, d)), s, z)
    return jnp.where(live[:, None, None], o.reshape(b, h, d), 0.0), s, z
