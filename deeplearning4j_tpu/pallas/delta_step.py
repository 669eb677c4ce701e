"""One position of the delta-rule recurrence, over the slots that owe a token.

``models/kda.py::kda_step`` is the recurrence of a decode step as XLA ops
over every slot of the serving pool:

    S' = a * S;  u = beta (v - S'^T k);  S_t = S' + k u^T;
    o  = S_t^T q = S'^T q + (k . q) u

with ``a = exp(g)`` one number a key channel (KDA) or one a head (Gated
DeltaNet). A slot that owes no token takes ``g = 0, beta = 0`` there: its
``[H, dk, dv]`` float32 matrix is read for the two products, multiplied by
one and written back, and a live slot's is read twice and written once.
This kernel is the same arithmetic with a work list, in place:

- the list is the slots with ``live`` true, lowest first, and their count
  (``reached_experts.work_list``, the reached experts' own function),
  scalar-prefetched. The grid is ``(slots, head groups)``: entry ``i``
  below the count is slot ``idx[i]``; an entry past it does nothing
  (``pl.when``) and its blocks are the last live entry's last, so nothing
  is fetched for it and nothing is written twice;
- the state is aliased to the output (``input_output_aliases``): a live
  slot's matrix is read once and written once, a slot that is not live is
  neither read nor written and keeps its bits. (No slot live at all: one
  block of slot 0 is copied through, which a pipeline's last write-back
  needs to be defined.) ``o`` stays in VMEM whole, starts as zeros and is
  written once: a dead row's ``o`` is zeros.

float32 in, float32 arithmetic on the VPU, float32 state: no product here
goes through the MXU, so no ``precision`` applies. Per head the matrix is
``dk / 8`` vregs a lane tile of ``dv``; ``k``, ``q`` and a per-channel ``a``
arrive with ``dk`` on the lanes and are transposed once a block to meet the
matrix's rows (``dk`` on the sublanes), ``v``, ``u`` and ``o`` have ``dv`` on
the lanes as the matrix has.

A block is a slot's whole ``[32, 128, 128]`` at the two serving cells'
sizes (``_GROUP`` 32 heads: 2 MiB in and 2 MiB out, each twice for the
pipeline), chosen on the chip (TPU v5e, PR 45, ``scripts/delta_step_bench.py``:
one layer's state of 64 slots, 100 positions in one loop over a donated
state): blocks of 8 / 16 / 32 heads take 0.194 / 0.167 / **0.155** ms at 20
live slots, 0.245 / 0.223 / **0.212** at 29 and 0.454 / 0.432 / **0.431** at
64, where ``kda_step`` takes 0.61 at any occupancy; a decay a channel costs
0.159 / 0.215 / 0.433. That is 6.3 us a live slot (4.2 MB moved: 670 GB/s) and
0.03 ms that do not scale: the pipeline's first fetch and last write-back,
and the grid's entries past the list, ``(slots - live) x groups`` of them,
which is why the larger block does better at low occupancy and no worse at
full.

Every process that builds a decode program traces and lowers this kernel
before its compile cache can answer, and that is set-up time a benchmark
cell is held to: a block of 32 heads unrolled in the kernel's text, once a
layer, added 0.9 s to the 1.1 s the decode program's trace and lowering
take on this sandbox's CPU (2.6 s of ``setup_s`` on the chip's host, PR 45).
So the heads run eight at a time under a loop (``_heads``, ``_SUB``) and the
layers of a model share one jitted function (``_delta_step``): the
program's trace and lowering read 0.9 s, no more than the parent's.

``interpret=True`` runs the same kernel through the Pallas interpreter
(CPU tests; the default where no TPU is attached).
"""

from __future__ import annotations

import functools
import importlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.pallas.reached_experts import work_list

__all__ = ["delta_step", "step_heads"]

# the module: the package gives its name to the function it re-exports
_flash = importlib.import_module("deeplearning4j_tpu.pallas.flash_attention")

_GROUP = 32                 # heads a block (the module's docstring)
_SUB = 8                    # heads unrolled in the kernel's text (_heads)
_VMEM_BYTES = 32 << 20


def step_heads(heads: int) -> int:
    """Heads a block: the largest divisor of ``heads`` up to ``_GROUP``
    that is ``heads`` itself or fills whole sublane tiles (the small
    operands' blocks are ``[group, dk]``)."""
    for group in range(min(heads, _GROUP), 0, -1):
        if heads % group == 0 and (group == heads or group % 8 == 0):
            return group
    return heads


def _kernel(n_ref, idx_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref,
            o_ref, s_out, *, group, sub):
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(n == 0)        # no list: the one block the pipeline holds
        def _():
            s_out[...] = s_ref[...]

    @pl.when(i < n)
    def _():
        lax.fori_loop(0, group // sub, functools.partial(
            _heads, refs=(q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref,
                          o_ref, s_out), sub=sub,
            slot=idx_ref[i], first=j * group), 0)


def _heads(c, carry, *, refs, sub, slot, first):
    """``sub`` heads of the block, from its head ``c * sub`` on: what the
    loop of ``_kernel`` runs. The heads inside are unrolled (their columns
    are static lane slices of one transpose); the loop over such runs keeps
    the kernel's text, and the time to trace and lower it in every process
    that builds the decode program, at one run's."""
    q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, o_ref, s_out = refs
    at_ = c * sub
    rows = pl.ds(pl.multiple_of(at_, sub), sub)
    k, q, v = k_ref[0, rows, :], q_ref[0, rows, :], v_ref[0, rows, :]
    g = g_ref[0, rows, :]                               # [sub, dk | 1]
    per_channel = g.shape[-1] != 1
    # one decay a head: spread along the lanes before the exp, so that a
    # head's is a row to lay down the sublanes ([1, 1] does not broadcast
    # both ways at once)
    a = jnp.exp(g if per_channel else jnp.broadcast_to(g, v.shape))
    beta = beta_ref[0, rows, :]                         # [sub, 1]
    kq = jnp.sum(k * q, axis=-1, keepdims=True)         # [sub, 1]
    kt, qt = k.T, q.T                                   # [dk, sub]
    at = a.T if per_channel else a
    out = []
    for h in range(sub):
        decay = at[:, h:h + 1] if per_channel else at[h:h + 1]
        s = decay * s_ref[0, at_ + h]                   # S' [dk, dv]
        kc = kt[:, h:h + 1]                             # [dk, 1]
        sk = jnp.sum(s * kc, axis=0, keepdims=True)     # [1, dv]
        sq = jnp.sum(s * qt[:, h:h + 1], axis=0, keepdims=True)
        u = beta[h:h + 1] * (v[h:h + 1] - sk)
        s_out[0, at_ + h] = s + kc * u
        out.append(sq + kq[h:h + 1] * u)
    o_ref[slot, pl.ds(pl.multiple_of(first + at_, sub), sub), :] = (
        jnp.concatenate(out, axis=0))
    return carry


def delta_step(q, k, v, g, beta, state, live=None, *,
               heads: Optional[int] = None,
               interpret: Optional[bool] = None):
    """``kda_step`` for the rows of ``live``: ``q, k`` [b, H, dk], ``v`` [b,
    H, dv], ``g`` [b, H, dk] (log-decay a channel) or [b, H, 1] (one a
    head), ``beta`` [b, H], ``state`` [b, H, dk, dv], all float32; ``live``
    [b] (bool; None: every row). Returns ``(o [b, H, dv], state)``: a row
    that is not live has ``o`` zero and its state's bits; ``state`` is
    updated in place where the caller donates it. ``heads``: heads a block
    (``step_heads``). ``interpret``: None = where no TPU is attached
    (``flash_default_interpret``)."""
    if interpret is None:
        interpret = _flash.flash_default_interpret()
    if live is None:
        live = jnp.ones((q.shape[0],), bool)
    return _delta_step(q, k, v, g, beta, state, live,
                       group=heads or step_heads(q.shape[1]),
                       interpret=interpret)


# jitted: a model's layers share one trace and one lowered function
@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def _delta_step(q, k, v, g, beta, state, live, *, group, interpret):
    b, h, dk = q.shape
    dv = v.shape[-1]
    if h % group:
        raise ValueError(f"{h} heads do not split into blocks of {group}")
    groups = h // group

    def entry(i, j, n_ref, idx_ref):
        # past the list: the last live entry's last block, fetched already
        last = jnp.maximum(n_ref[0] - 1, 0)
        return (idx_ref[jnp.minimum(i, last)],
                jnp.where(i < n_ref[0], j, groups - 1))

    def rows(width):
        return pl.BlockSpec(
            (1, group, width), lambda i, j, n, idx: (*entry(i, j, n, idx), 0))

    matrix = pl.BlockSpec(
        (1, group, dk, dv), lambda i, j, n, idx: (*entry(i, j, n, idx), 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, group=group,
                          sub=_SUB if group % _SUB == 0 else group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups),
            in_specs=[rows(dk), rows(dk), rows(dv), rows(g.shape[-1]),
                      rows(1), matrix],
            out_specs=[pl.BlockSpec((b, h, dv), lambda i, j, n, idx:
                                    (0, 0, 0)), matrix]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 7 counts the two scalar operands: the state
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(*work_list(live), q, k, v, g, beta[..., None], state)
    return o, state
