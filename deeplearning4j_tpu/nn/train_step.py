"""The optimizer step of the config-DSL stack, written once.

``MultiLayerNetwork`` and ``ComputationGraph`` train with the same
algorithm: *(plain | accumulated) loss and grads -> (apply | sentinel-
guarded apply) -> optional metrics pack*. This module owns it —
``optimizer_step`` — and every program that scans it: the whole-epoch
chunk program (``epoch_run_fn``), the K-step and TBPTT scans, and the host
driver behind every ``fit_epochs`` (``run_fused_epochs``).

A batch is ONE pytree ``(inputs, labels, feature_masks, label_masks)``:
arrays for ``MultiLayerNetwork``, tuples per input / output position for
``ComputationGraph`` (``None`` where a mask is absent). Everything here is
a ``tree_map`` over it, so nothing asks which class it holds. A network
supplies what truly differs between the classes:

- ``_loss_and_state(params, net_state, *batch, rng, train, rnn_state)``
  and ``_micro_loss(params, net_state, batch, rng, d_full, k)`` — the
  full-batch loss and one micro-batch's share of it;
- ``_apply_updaters`` / ``_lr_scale`` (they read ``updater_specs``) and
  ``_policy``.

The free functions take ``net`` first: a trainer for any ``(params,
batch) -> loss`` (ROADMAP D1) can call them with a model that is neither
class.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import dtypes as dtypes_mod
from deeplearning4j_tpu.analysis.annotations import traced
from deeplearning4j_tpu.perf.epoch_cache import (
    accum_steps_default,
    drive_epoch_chunks,
    effective_accum_steps,
    elastic_reshard,
    epoch_schedule,
    stream_epochs,
)
from deeplearning4j_tpu.scopes import scope

__all__ = ["loss_grads", "accum_grads", "optimizer_step", "epoch_run_fn",
           "multi_step_fn", "tbptt_fn", "jit_step", "epoch_train_step",
           "step_state", "run_fused_epochs", "fit_epochs"]


@traced
def loss_grads(net, params, net_state, batch, rng, rnn_state=None):
    """Training loss + gradients of one batch (pure; the caller holds the
    dtype policy scope). Returns ``((loss, (net_state, rnn_state)),
    grads)``."""
    def loss_fn(p):
        return net._loss_and_state(p, net_state, *batch, rng, train=True,
                                   rnn_state=rnn_state)

    return jax.value_and_grad(loss_fn, has_aux=True)(params)


@traced
def accum_grads(net, params, net_state, batch, rng, accum_steps: int):
    """Loss + summed gradients of one batch taken as ``accum_steps``
    micro-batches: an inner ``lax.scan`` computes each micro-batch's
    share of the FULL-batch masked-mean loss (``net._micro_loss``: its
    masked sum over the full batch's per-head mask denominator, plus 1/K
    of the L1/L2 penalty) and sums the gradients. By linearity this is
    the unaccumulated gradient up to f32 summation order, while the live
    activation working set shrinks by K. Caveats
    (docs/training_pipeline.md): dropout draws per micro-batch, and
    train-mode batchnorm statistics chain K per-micro-batch updates
    instead of one full-batch update. Returns ``(grads, loss,
    net_state)``."""
    k = accum_steps
    micro = jax.tree_util.tree_leaves(batch)[0].shape[0] // k

    def split(a):
        # STRIDED split (row i -> micro-batch i % k): under a
        # batch-sharded mesh every micro-batch then spans all shards
        # evenly, so the slice stays shard-local (a contiguous split
        # would pull each micro-batch from a subset of the shards and
        # force a resharding exchange)
        return jnp.moveaxis(a.reshape((micro, k) + a.shape[1:]), 1, 0)

    d_full = jax.tree_util.tree_map(
        lambda m: jnp.maximum(jnp.sum(m), 1.0), batch[3])
    seq = (jax.tree_util.tree_map(split, batch), jax.random.split(rng, k))

    def body(carry, inp):
        gsum, lsum, nst_in = carry
        # grads wrt params only (argnum 0); net_state threads through
        # the carry so NO micro-batch's update is dropped. Accumulation
        # buffers carry the PARAM dtype: bf16 micro-batch grads
        # (master-weights policy) upcast into the f32 sum instead of
        # summing in bf16
        micro_batch, micro_rng = inp
        (lval, st), g = jax.value_and_grad(net._micro_loss, has_aux=True)(
            params, nst_in, micro_batch, micro_rng, d_full, k)
        gsum = jax.tree_util.tree_map(
            lambda s, gg: s + gg.astype(s.dtype), gsum, g)
        return (gsum, lsum + lval, st), None

    zeros = net._policy.grad_zeros(params)
    (grads, loss, new_net_state), _ = jax.lax.scan(
        body, (zeros, jnp.zeros((), jnp.float32), net_state), seq)
    return grads, loss, new_net_state


@traced
def optimizer_step(net, params, updater, net_state, iteration,
                   lr_scale_host, batch, rng, rnn_state=None, *,
                   accum_steps: int = 1, guard: bool = False,
                   metrics_stride: int = 0):
    """ONE optimizer step — forward, loss (+L1/L2), backward, gradient
    normalization, updater math, parameter update — in three stages:

    1. **grads**: ``loss_grads``, or ``accum_grads`` when ``accum_steps
       > 1`` (which threads no rnn carry).
    2. **apply**: LR schedule times ``lr_scale_host`` (a traced scalar,
       ALWAYS applied — the ``halve_lr`` policy and the SCORE decay
       adjust it between dispatches without recompiling) + updater.
       ``guard`` wraps it in the numeric sentinel: the step trips when
       the loss or ANY gradient element is non-finite and ``lax.cond``
       carries params/updater/net state through unchanged, containing a
       poisoned batch to exactly one skipped update. The iteration
       counter is the caller's and advances either way, so LR schedules
       stay aligned with an uninterrupted run; the raw (possibly
       non-finite) loss is returned — the host-side ``DL4J_NAN_GUARD``
       policy reads the trip flags, not the losses (resilience/guard.py).
    3. **pack**: ``metrics_stride > 0`` appends the ``[4]`` f32
       diagnostics vector (``monitor.pack.step_metrics``: grad / applied-
       update / param global-norm, effective lr scale). Observational:
       params are bitwise those of the same step without it.

    The three keywords are trace-time constants (the ``_epoch_steps``
    key). Returns ``(params, updater, net_state, loss, rnn_state,
    tripped, metrics)`` — always this order, ``None`` where a part is
    compiled out."""
    policy = net._policy
    with dtypes_mod.policy_scope(policy):
        # master-weights policy: ONE bf16 copy for forward/backward,
        # grads upcast ONCE, updater applies to the f32 masters
        # (identity casts under the single-dtype policies)
        with scope("dsl.cast"):
            fwd_params = policy.compute_copy(params)
        if accum_steps > 1:
            grads, loss, stepped_state = accum_grads(
                net, fwd_params, net_state, batch, rng, accum_steps)
            new_rnn = None
        else:
            (loss, (stepped_state, new_rnn)), grads = loss_grads(
                net, fwd_params, net_state, batch, rng, rnn_state)
        # sentinel + telemetry norms read the f32 grads (post-upcast): a
        # bf16 overflow to inf is preserved by the widening cast
        with scope("dsl.cast"):
            grads = policy.master_grads(grads)

        def apply(_=None):
            with scope("dsl.update"):
                p2, u2 = net._apply_updaters(params, updater, grads,
                                             iteration, lr_scale_host)
            return p2, u2, stepped_state

        if guard:
            from deeplearning4j_tpu.resilience.guard import tree_all_finite

            with scope("dsl.update"):
                ok = jnp.isfinite(loss) & tree_all_finite(grads)
            new_params, new_updater, new_state = jax.lax.cond(
                ok, apply, lambda _: (params, updater, net_state), None)
            tripped = ~ok
        else:
            new_params, new_updater, new_state = apply()
            tripped = None
        metrics = None
        if metrics_stride:
            from deeplearning4j_tpu.monitor.pack import step_metrics

            with scope("dsl.update"):
                metrics = step_metrics(
                    params, new_params, grads,
                    net._lr_scale(iteration, lr_scale_host), iteration,
                    metrics_stride)
    return (new_params, new_updater, new_state, loss, new_rnn, tripped,
            metrics)


@traced
def epoch_run_fn(net, shuffle: bool, accum_steps: int = 1,
                 guard: bool = False, metrics_stride: int = 0):
    """The PURE chunk program: chunk_epochs x n_batches optimizer steps
    — outer ``lax.scan`` over epoch keys (each epoch derives a
    device-side ``jax.random.permutation`` batch order + per-batch step
    keys via ``epoch_schedule``; the permutation runs over the UNSHARDED
    batch-index axis, so on a mesh the gathers stay shard-local and no
    resharding collective is emitted), inner scan gathering batches from
    the resident ``[N, B, ...]`` stacks and running ``optimizer_step``
    with this key's ``accum_steps`` / ``guard`` / ``metrics_stride``.
    Outputs, in order: ``(params, updater, net_state, [E, N] hist[,
    [E, N] trips][, [E, N, 4] metrics])`` — trips present iff guarded,
    metrics present iff the pack is compiled in. Shared verbatim by the
    single-device jit and ``ParallelWrapper``'s SPMD jit (which pins
    out_shardings)."""

    def run(params, updater_state, net_state, iteration0, lr_scale_host,
            xs, ys, fms, lms, epoch_keys):
        stacks = (xs, ys, fms, lms)
        n = jax.tree_util.tree_leaves(xs)[0].shape[0]

        def epoch_body(carry, ekey):
            with scope("dsl.data"):
                order, step_keys = epoch_schedule(ekey, n, shuffle)

            def batch_body(c2, inp):
                params, upd, nst, it = c2
                i, rng = inp
                with scope("dsl.data"):
                    batch = jax.tree_util.tree_map(lambda a: a[i], stacks)
                p2, u2, s2, loss, _, tripped, m = optimizer_step(
                    net, params, upd, nst, it, lr_scale_host, batch, rng,
                    accum_steps=accum_steps, guard=guard,
                    metrics_stride=metrics_stride)
                return (p2, u2, s2, it + 1), (loss, tripped, m)

            return jax.lax.scan(batch_body, carry, (order, step_keys))

        carry0 = (params, updater_state, net_state, iteration0)
        (p, u, s, _), hist = jax.lax.scan(epoch_body, carry0, epoch_keys)
        return (p, u, s) + tuple(h for h in hist if h is not None)

    return run


def multi_step_fn(net):
    """K optimizer steps on ONE batch fused into one XLA program via
    ``lax.scan`` over the step keys — the batch transfers once and there
    is a single host dispatch per K steps, eliminating per-step launch
    overhead for small models (the equivalent of the reference's
    ``iterations(n)`` inner loop, but compiled). Returns ``(params,
    updater, net_state, rnn_state, last loss)``."""

    def multi(params, updater_state, net_state, iteration0, lr_scale_host,
              batch, rngs, rnn_state):
        def body(carry, rng):
            params, upd, nst, rnn, it = carry
            p2, u2, s2, loss, rnn2, _, _ = optimizer_step(
                net, params, upd, nst, it, lr_scale_host, batch, rng, rnn)
            return (p2, u2, s2, rnn2, it + 1), loss

        carry0 = (params, updater_state, net_state, rnn_state, iteration0)
        (p, u, s, rnn, _), losses = jax.lax.scan(body, carry0, rngs)
        return p, u, s, rnn, losses[-1]

    return multi


def tbptt_fn(net):
    """ALL full TBPTT windows of a batch fused into ONE XLA program:
    ``lax.scan`` over windows, each window one optimizer step with the
    rnn carry threaded through and ``stop_gradient`` applied at window
    boundaries (truncation). The sequence transfers to the device once
    and there is a single host dispatch per batch instead of one per
    window (the reference walks windows host-side —
    MultiLayerNetwork.java:1150, ComputationGraph.java:489-534).
    Temporal ``[b, t, ...]`` features/labels and every ``[b, t]`` mask
    are cut into windows; static arrays (2D labels, which stay whole per
    window as in ``DataSet.slice_time``; an image conditioning a caption
    LSTM) are closed over and reused every window. Returns ``(params,
    updater, net_state, rnn_state, last loss)``."""
    window = net.conf.tbptt_fwd_length

    def tbptt(params, updater_state, net_state, iteration0, lr_scale_host,
              batch, rngs, rnn_state0):
        flat, treedef = jax.tree_util.tree_flatten(batch)
        n_data = len(jax.tree_util.tree_leaves(batch[:2]))
        windowed = [j >= n_data or a.ndim == 3 for j, a in enumerate(flat)]
        t = max(a.shape[1] for a in jax.tree_util.tree_leaves(batch[0])
                if a.ndim == 3)
        n_win = t // window

        def to_windows(a):  # [b, t, ...] -> [n_win, b, window, ...]
            shaped = a.reshape((a.shape[0], n_win, window) + a.shape[2:])
            return jnp.moveaxis(shaped, 1, 0)

        with scope("dsl.data"):
            wins = [to_windows(a) for a, w in zip(flat, windowed) if w]

        def body(carry, inp):
            params, upd, nst, rnn, it = carry
            cut, rng = inp
            cut = iter(cut)
            cur = treedef.unflatten(
                [next(cut) if w else a for a, w in zip(flat, windowed)])
            p2, u2, s2, loss, rnn2, _, _ = optimizer_step(
                net, params, upd, nst, it, lr_scale_host, cur, rng, rnn)
            rnn2 = jax.tree_util.tree_map(jax.lax.stop_gradient, rnn2)
            return (p2, u2, s2, rnn2, it + 1), loss

        carry0 = (params, updater_state, net_state, rnn_state0, iteration0)
        (p, u, s, rnn, _), losses = jax.lax.scan(body, carry0, (wins, rngs))
        return p, u, s, rnn, losses[-1]

    return tbptt


def jit_step(net, **jit_kwargs):
    """``optimizer_step`` jitted for the per-step paths (``fit``, the
    wrapper's sharded step, the ``DL4J_NAN_GUARD=raise`` replay):
    ``step(params, updater, net_state, iteration, lr_scale_host, batch,
    rng, rnn_state=None, accum_steps=1)`` with params/updater/net state
    donated unless ``jit_kwargs`` says otherwise. These paths are NOT
    sentinel-guarded and carry no metrics pack."""

    def step(params, updater, net_state, iteration, lr_scale_host, batch,
             rng, rnn_state=None, accum_steps=1):
        return optimizer_step(net, params, updater, net_state, iteration,
                              lr_scale_host, batch, rng, rnn_state,
                              accum_steps=accum_steps)

    jit_kwargs.setdefault("donate_argnums", (0, 1, 2))
    return jax.jit(step, static_argnames="accum_steps", **jit_kwargs)


def epoch_train_step(net, shuffle: bool, accum_steps: int = 1,
                     guard: bool = False, metrics_stride: int = 0):
    """Jitted fused epoch program (one entry of ``net._epoch_steps`` per
    (shuffle, accum, guard, metrics_stride)); params/updater/net state
    are donated; the dataset stacks are NOT (they stay in HBM across
    chunks). Cached entries are :class:`ProfiledProgram`s: with
    ``DL4J_PROFILE`` off every call passes through to the jit function
    untouched; on, each program's cost/memory analysis is captured once
    per signature (monitor/profile.py)."""
    from deeplearning4j_tpu.monitor.profile import ProfiledProgram

    key = (shuffle, accum_steps, guard, metrics_stride)
    fn = net._epoch_steps.get(key)
    if fn is None:
        fn = ProfiledProgram(
            jax.jit(net._epoch_run_fn(*key), donate_argnums=(0, 1, 2)),
            name=type(net).__name__, key=key)
        net._epoch_steps[key] = fn
    return fn


def step_state(net):
    """The five leading arguments of every train program, from the
    network's live state: ``(params, updater_state, net_state,
    iteration, lr_scale_host)``."""
    return (net.params, net.updater_state, net.net_state,
            jnp.asarray(net.iteration_count, jnp.int32),
            jnp.asarray(net._lr_scale_host, jnp.float32))


def run_fused_epochs(net, cache, num_epochs: int, chunk_epochs, program, *,
                     shuffle: bool, accum_steps: int, guard, telemetry,
                     on_chunk, reshard, mesh=None):
    """The host driver body behind every ``fit_epochs``: resolves the
    program key, and hands ``drive_epoch_chunks`` the two closures that
    touch the train programs. ``program(shuffle, accum, guarded,
    stride)`` returns the jitted chunk program — the network's
    ``_epoch_train_step`` or ``ParallelWrapper``'s ``out_shardings``-
    pinned one, which also passes ``mesh``: a callable giving the mesh
    to run under NOW (an elastic reshard swaps it mid-run)."""
    from deeplearning4j_tpu.monitor import fused_metrics_stride
    from deeplearning4j_tpu.resilience.guard import nan_guard_policy

    accum = effective_accum_steps(accum_steps, cache.batch)
    guard = nan_guard_policy() if guard is None else guard
    guarded = guard != "off"
    stride = fused_metrics_stride(telemetry)

    def on_mesh():
        return contextlib.nullcontext() if mesh is None else mesh()

    def launch(epoch_keys):
        # resolved per launch, not per run: an elastic TOPOLOGY reshard
        # clears the program cache (the flat-vs-per-layer updater-apply
        # choice is baked in at trace time from the live placements, and
        # the wrapper's programs are pinned to their mesh), so this must
        # pick up the program traced for the NEW placements
        step = program(shuffle, accum, guarded, stride)
        with on_mesh():
            out = step(*step_state(net), *cache.stacks, epoch_keys)
        net.params, net.updater_state, net.net_state = out[:3]
        return (out[3], out[4] if guarded else None,
                out[-1] if stride else None)

    def replay_step(params, upd, nst, it, i, rng):
        # per-step replay for DL4J_NAN_GUARD=raise localization: the
        # same step math on the same cache slice with the same key —
        # including the accumulation split, whose per-micro-batch rng
        # draws the fused run consumed, and the host LR scale the fused
        # run applied. Runs on the replicated layout; fine as a
        # pre-raise diagnostic even under FSDP, where it temporarily
        # re-replicates the state it is about to abort with
        batch = jax.tree_util.tree_map(lambda a: a[i], cache.stacks)
        with on_mesh():
            p, u, s, loss, *_ = net._train_step(
                params, upd, nst, jnp.asarray(it, jnp.int32),
                jnp.asarray(net._lr_scale_host, jnp.float32), batch, rng,
                accum_steps=accum)
        return p, u, s, loss

    return drive_epoch_chunks(net, cache, num_epochs, chunk_epochs, launch,
                              shuffle=shuffle, guard=guard,
                              replay_step=replay_step, on_chunk=on_chunk,
                              reshard=reshard)


def fit_epochs(net, data, num_epochs: int, cache_cls, per_step_configs: str,
               *, shuffle, chunk_epochs, cache_mb, mesh, accum_steps, guard,
               telemetry, on_chunk):
    """Both network classes' ``fit_epochs`` (the contract is on
    ``MultiLayerNetwork.fit_epochs``): the fallback matrix — a
    configuration outside ``net.fused_epochs_supported()``
    (``per_step_configs`` names them) runs the plain per-step loop, a
    dataset over the HBM budget streams — then the cache of class
    ``cache_cls`` is built or taken prebuilt, the trainable state placed
    on its mesh, and the fused run driven. Returns the ``[E, N]`` loss
    history, or ``None`` when a fallback ran."""
    from deeplearning4j_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    net._ensure_init()
    if num_epochs <= 0:
        return None
    if accum_steps is None:
        accum_steps = accum_steps_default()
    prebuilt = isinstance(data, cache_cls)
    if not net.fused_epochs_supported():
        if prebuilt:
            raise ValueError(
                "this configuration needs the per-step fit loop "
                f"({per_step_configs}) — pass the original iterator, not "
                f"a {cache_cls.__name__}")
        for _ in range(num_epochs):
            net.fit(data)
        return None
    cache = data if prebuilt else cache_cls.build(
        data, budget_mb=cache_mb, mesh=mesh, accum_steps=accum_steps)
    if cache is None:
        stream_epochs(net, data, num_epochs)
        return None
    if cache.mesh is not None:
        net._place_on_mesh(cache.mesh)
    return run_fused_epochs(
        net, cache, num_epochs, chunk_epochs, net._epoch_train_step,
        shuffle=shuffle, accum_steps=accum_steps, guard=guard,
        telemetry=telemetry, on_chunk=on_chunk,
        reshard=lambda m: elastic_reshard(net, cache, m))
