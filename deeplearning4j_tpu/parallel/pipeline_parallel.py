"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pipe`` axis.

Greenfield relative to the reference (SURVEY §2.5: "NOT present in the
reference: ... pipeline parallelism"), but required of a modern TPU
framework. Expressed the SPMD way: every device runs the SAME program under
``shard_map``; stage identity comes from ``lax.axis_index`` and activations
hop stage→stage with ``lax.ppermute`` over ICI. There is no per-stage Python
program — XLA compiles one step for all stages.

Schedule: GPipe with M microbatches over S stages — T = M + S - 1 ticks.
Each tick every stage (a) selects its input (stage 0 ingests microbatch t,
others take the activation handed to them last tick), (b) applies its stage
fn, (c) permutes the result one hop down the ring. Bubble fraction is
(S-1)/T, so choose M >> S. Gradients flow through ``ppermute`` natively, so
``jax.grad`` of a pipelined loss is the pipelined backward pass — the
backward schedule mirrors the forward automatically.

Stages must be homogeneous (same activation shape in/out), the natural
regime for stacked transformer blocks / equal-width dense towers. Stage
params are stored stacked on a leading [S, ...] axis sharded over ``pipe``,
so each device materializes only its own stage's weights.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import PIPE_AXIS


def stack_stage_params(per_stage_params) -> Any:
    """[{...}, {...}, ...] per-stage pytrees → one pytree with leading [S]
    axis on every leaf (the layout ``spmd_pipeline`` consumes)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def shard_stage_params(stacked, mesh: Mesh, axis_name: str = PIPE_AXIS):
    """Place stacked stage params so each device holds only its stage."""
    from deeplearning4j_tpu.parallel.mesh import shard_leading_axis
    return shard_leading_axis(stacked, mesh, axis_name)


def spmd_pipeline(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    x_microbatches: jnp.ndarray,
    mesh: Mesh,
    *,
    axis_name: str = PIPE_AXIS,
) -> jnp.ndarray:
    """Run microbatches through the stage pipeline.

    - ``stage_fn(params, x) -> y`` with y.shape == x.shape (homogeneous).
    - ``stage_params``: pytree whose leaves have leading dim S (stacked
      stages), sharded over ``axis_name``.
    - ``x_microbatches``: [M, mb, ...] microbatches (replicated; only stage 0
      reads them).

    Returns [M, mb, ...] outputs, replicated across the pipe axis.
    """
    if axis_name not in mesh.shape:
        # size-1 pipe axis is dropped from the mesh: run stages sequentially
        n = jax.tree.leaves(stage_params)[0].shape[0]
        out = x_microbatches
        for s in range(n):
            p = jax.tree.map(lambda a: a[s], stage_params)
            out = jax.vmap(lambda xb: stage_fn(p, xb))(out)
        return out
    n_stages = mesh.shape[axis_name]
    n_micro = x_microbatches.shape[0]
    leaves = jax.tree.leaves(stage_params)
    if leaves and leaves[0].shape[0] != n_stages:
        raise ValueError(
            f"stage_params stack {leaves[0].shape[0]} stages but mesh axis "
            f"'{axis_name}' has {n_stages} devices")
    # Remaining mesh axes (e.g. 'data') shard the microbatch rows: each
    # replica row of the mesh pipelines its own slice of the batch.
    extra_axes = tuple(n for n in mesh.axis_names if n != axis_name)
    x_spec = P(None, extra_axes) if extra_axes else P()

    def body(params, x):
        # params leaves arrive as [1, ...] (this device's stage) — unstack.
        params = jax.tree.map(lambda p: p[0], params)
        stage = lax.axis_index(axis_name)
        n_ticks = n_micro + n_stages - 1
        state = jnp.zeros_like(x[0])          # activation handed to me
        outputs = jnp.zeros_like(x)           # filled on the last stage
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, carry):
            state, outputs = carry
            ingest = lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            cur = jnp.where(stage == 0, ingest, state)
            out = stage_fn(params, cur)
            mb_idx = t - (n_stages - 1)
            upd = lax.dynamic_update_index_in_dim(
                outputs, out, jnp.clip(mb_idx, 0, n_micro - 1), 0)
            valid = jnp.logical_and(stage == n_stages - 1, mb_idx >= 0)
            outputs = jnp.where(valid, upd, outputs)
            state = lax.ppermute(out, axis_name, fwd)
            return state, outputs

        _, outputs = lax.fori_loop(0, n_ticks, tick, (state, outputs))
        # Only the last stage holds real outputs; replicate via masked psum.
        outputs = jnp.where(stage == n_stages - 1, outputs, 0.0)
        return lax.psum(outputs, axis_name)

    p_spec = jax.tree.map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), stage_params)
    return shard_map(
        body, mesh=mesh,
        in_specs=(p_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x_microbatches)


def split_microbatches(x: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    """[B, ...] → [M, B/M, ...]."""
    if x.shape[0] % n_micro:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by {n_micro} microbatches")
    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])


def pipeline_train_step(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    loss_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    mesh: Mesh,
    *,
    n_microbatches: int,
    learning_rate: float = 0.1,
    axis_name: str = PIPE_AXIS,
):
    """Build a jitted SGD train step for a pipelined tower.

    ``loss_fn(y_pred, y_true) -> scalar`` is applied to the re-flattened
    last-stage outputs. ``jax.grad`` differentiates through the pipeline
    (ppermute transposes to the reverse permute), yielding the backward
    pipeline schedule for free.
    """
    def loss_of(params, x, y):
        xm = split_microbatches(x, n_microbatches)
        out = spmd_pipeline(stage_fn, params, xm, mesh, axis_name=axis_name)
        return loss_fn(out.reshape((-1,) + out.shape[2:]), y)

    @jax.jit
    def step(params, x, y):
        loss, grads = jax.value_and_grad(loss_of)(params, x, y)
        params = jax.tree.map(lambda p, g: p - learning_rate * g,
                              params, grads)
        return params, loss

    return step
