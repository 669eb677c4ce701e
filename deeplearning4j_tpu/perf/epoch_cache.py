"""Device-resident dataset cache + key schedule for whole-epoch fusion.

Two costs dominate every small/medium config: the host dispatch of each
jitted call and the host->device transfer of each batch (neither has been
measured on a directly attached chip yet — PERF.md). ``fit(iterator)`` pays
both once per batch, every epoch, re-feeding the same data it fed last epoch — for the reference's workhorse
pattern (MNIST/LFW-scale datasets iterated for many epochs) that is E*N
dispatches and E*N transfers of bytes that never change.

``DeviceDataSetCache`` drains a ``DataSetIterator`` ONCE, pads every batch up
the shape-bucket ladder (``perf.bucketing`` — one uniform bucket, the max
across batches, so the whole dataset stacks), and ships the stack to HBM as
single ``[N, B, ...]`` arrays: one transfer per array for the entire training
run. ``fit_epochs`` on both network classes then scans E epochs x N batches
inside ONE donated XLA program — ``lax.scan`` over a per-epoch device-side
``jax.random.permutation`` reshuffle with per-batch RNG keys — returning the
loss history as a single ``[E, N]`` device array. One dispatch and zero
re-transfers per training run instead of E*N of each.

The cache respects an HBM budget (``DL4J_DEVICE_CACHE_MB``, default 2048):
``build`` returns ``None`` — never raises — when the padded dataset would
exceed it (or when batches cannot stack: ragged feature ranks, missing
labels), and callers fall back to the streaming path with N-deep async device
prefetch so the link overlaps compute instead of serializing with it.

Mesh-aware (SPMD) caching: pass ``mesh=`` and the ``[N, B, ...]`` stacks are
placed with a ``NamedSharding`` that shards the BATCH axis (axis 1) over the
mesh's ``data`` axis — each chip holds only ``B/n_dp`` rows of every batch,
so the budget check becomes per-shard and the cacheable dataset size scales
linearly with chip count. The per-epoch reshuffle permutes the (unsharded)
batch-index axis N, so the fused program's gathers are shard-local and GSPMD
emits no resharding collective for the shuffle; the only per-step collective
is the gradient all-reduce. When the bucket batch does not divide the data
axis the stacks fall back to replicated placement (sharding here is an
optimization, never a semantics change).

Two more knobs tighten the per-chip HBM model (PERF.md §Round-8):
``DL4J_CACHE_DTYPE=bfloat16`` stores the features/labels stacks in the
compute dtype (masks stay f32), halving the resident footprint — fused-vs-
per-step equivalence stays bitwise (both paths read the same cache) but
results differ from full-f32 training by normal bf16 rounding. And
``accum_steps=K`` (gradient accumulation) divides the per-step working-set
term of the budget by K: the fused scan's live batch slice plus its
gradient-side activations scale with the microbatch, so global batches whose
step working set would overflow a chip still take the fused path.

Pad rows are mask-inert through the loss (the labels mask is
created-or-extended with zeros, exactly ``bucketing.pad_dataset``), with the
same caveat: train-mode BatchNormalization computes batch statistics over all
rows, so padded TAIL batches skew its running averages — identical to
``BucketedDataSetIterator``'s documented behavior, not a new hazard.
"""

from __future__ import annotations

import logging
import os
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np
from deeplearning4j_tpu.analysis.annotations import traced

from deeplearning4j_tpu.perf.bucketing import bucket_size, pad_axis0

DEFAULT_CACHE_MB = 2048
DEFAULT_PREFETCH_DEPTH = 8


def cache_budget_mb() -> float:
    """HBM budget for the epoch cache. ``DL4J_DEVICE_CACHE_MB=0`` disables
    caching entirely (every fit_epochs call streams)."""
    raw = os.environ.get("DL4J_DEVICE_CACHE_MB", "")
    try:
        return float(raw) if raw else float(DEFAULT_CACHE_MB)
    except ValueError:
        return float(DEFAULT_CACHE_MB)


def prefetch_depth() -> int:
    """Device-prefetch buffer depth for the streaming fallback
    (``DL4J_PREFETCH_DEPTH``): how many batches the async producer keeps
    device-resident ahead of the consumer."""
    raw = os.environ.get("DL4J_PREFETCH_DEPTH", "")
    try:
        return max(1, int(raw)) if raw else DEFAULT_PREFETCH_DEPTH
    except ValueError:
        return DEFAULT_PREFETCH_DEPTH


def cache_dtype():
    """Storage dtype for the features/labels stacks (``DL4J_CACHE_DTYPE``).
    ``bfloat16``/``bf16`` halves the resident footprint; anything else
    (including unset) keeps the source dtype. Masks are never narrowed —
    they gate mask-weighted reductions and must stay exact."""
    raw = os.environ.get("DL4J_CACHE_DTYPE", "").strip().lower()
    if raw in ("bfloat16", "bf16"):
        import jax.numpy as jnp

        return jnp.bfloat16
    return None


def accum_steps_default() -> int:
    """Default gradient-accumulation factor for ``fit_epochs``
    (``DL4J_ACCUM_STEPS``, default 1 = no accumulation)."""
    raw = os.environ.get("DL4J_ACCUM_STEPS", "")
    try:
        return max(1, int(raw)) if raw else 1
    except ValueError:
        return 1


def effective_accum_steps(requested: int, batch: int) -> int:
    """Largest divisor of ``batch`` that is <= ``requested`` microbatches.
    Accumulation needs the bucket batch to split evenly; rather than fail
    a whole training run over an env default, clamp to the nearest
    feasible factor (logged, since a weaker K also weakens the budget
    relief the caller asked for)."""
    requested = max(1, int(requested))
    if requested <= 1 or batch <= 0:
        return 1
    batch = int(batch)
    k = next(d for d in range(min(requested, batch), 0, -1)
             if batch % d == 0)
    if k != requested:
        logging.getLogger(__name__).warning(
            "accum_steps=%d does not divide the bucket batch %d; "
            "clamped to %d", requested, batch, k)
    return k


def _data_shards(mesh) -> int:
    """Size of the mesh ``data`` axis (1 when mesh is None or the axis was
    dropped)."""
    from deeplearning4j_tpu.parallel.mesh import data_axis_size

    return data_axis_size(mesh)


def _batch_sharding(mesh, ndim: int):
    """NamedSharding for an ``[N, B, ...]`` stack: N replicated, B sharded
    over ``data``, trailing dims replicated."""
    from deeplearning4j_tpu.parallel.sharding_registry import batch_sharding

    if mesh is None:
        return None
    return batch_sharding(mesh, ndim, stacked=True)


def _place(arr, mesh, sharded: bool = True):
    """device_put ``arr`` with its batch axis sharded over the mesh's data
    axis; replicated over the mesh when ``sharded`` is False (the bucket
    batch did not tile the axis — same devices, no partitioning); plain
    single-device placement when mesh is None."""
    import jax

    if arr is None:
        return None
    if mesh is None:
        return jax.device_put(arr)
    if not sharded:
        from deeplearning4j_tpu.parallel.sharding_registry import (
            replicated_sharding)

        return jax.device_put(arr, replicated_sharding(mesh))
    return jax.device_put(arr, _batch_sharding(mesh, arr.ndim))


@traced
def epoch_schedule(epoch_key, n_batches: int, shuffle: bool):
    """(batch order, per-batch step keys) for one epoch, derived from one
    epoch key. Pure function of the key — the SAME derivation runs traced
    inside the fused epoch program and eagerly in the equivalence tests, so
    the two paths consume identical RNG streams by construction."""
    import jax
    import jax.numpy as jnp

    perm_key, step_key = jax.random.split(epoch_key)
    order = (jax.random.permutation(perm_key, n_batches) if shuffle
             else jnp.arange(n_batches))
    return order, jax.random.split(step_key, n_batches)


def _nbytes_padded(a, target_rows: int, itemsize: Optional[int] = None) -> int:
    """Bytes of ``a`` with axis 0 padded to ``target_rows`` (``itemsize``
    overrides the source dtype's — the DL4J_CACHE_DTYPE narrowed store)."""
    if a is None:
        return 0
    size = a.dtype.itemsize if itemsize is None else itemsize
    per_row = int(np.prod(a.shape[1:], dtype=np.int64)) * size
    return per_row * target_rows


def _host(a):
    """Gather to host numpy (device batches gather ONCE at build)."""
    return None if a is None else np.asarray(a)


def _stack_padded(arrays: Sequence, target: int) -> np.ndarray:
    return np.stack([_host(pad_axis0(_host(a), target)) for a in arrays])


def _host_label_mask(labels: np.ndarray, mask, target: int) -> np.ndarray:
    """Host-side twin of ``bucketing.padded_label_mask``: existing mask (or
    ones) extended with ZEROS so pad rows drop out of every mask-weighted
    reduction."""
    n = int(labels.shape[0])
    if mask is None:
        shape = (n,) if labels.ndim == 2 else (n, int(labels.shape[1]))
        mask = np.ones(shape, np.float32)
    return _host(pad_axis0(np.asarray(mask, np.float32), target))


def _drain(data) -> Optional[List[Any]]:
    """Materialize an iterator/list/DataSet into a host batch list."""
    if hasattr(data, "features"):  # a single (Multi)DataSet
        return [data]
    # DataSetIterator.__iter__ resets; plain lists/tuples iterate as-is
    return list(data)


class DeviceDataSetCache:
    """The whole dataset as four HBM-resident ``[N, B, ...]`` stacks.

    ``build`` drains the iterator once, bucket-pads every batch to ONE
    uniform bucket (the max rung any batch needs — a 100/100/56 epoch at
    batch 100 stacks as ``[3, 128, ...]``), and transfers each stacked
    array exactly once. Returns ``None`` (caller streams instead) when the
    padded stack would exceed the HBM budget or batches cannot stack.
    """

    def __init__(self, features, labels, features_mask, labels_mask,
                 n_batches: int, batch: int, total_examples: int,
                 nbytes: int, mesh=None, n_shard: int = 1):
        self.features = features          # [N, B, ...]
        self.labels = labels              # [N, B, ...]
        self.features_mask = features_mask  # [N, B, t] or None
        self.labels_mask = labels_mask    # [N, B(, t)] — always materialized
        self.n_batches = n_batches
        self.batch = batch
        self.total_examples = total_examples
        self.nbytes = nbytes              # total across all shards
        self.mesh = mesh                  # None = single-device placement
        self.n_shard = n_shard            # data-axis shards holding the stacks

    @property
    def stacks(self):
        """The train programs' batch pytree, stacked: ``(features,
        labels, feature mask, label mask)``, each ``[N, B, ...]``."""
        return (self.features, self.labels, self.features_mask,
                self.labels_mask)

    def respec(self, mesh) -> "DeviceDataSetCache":
        """Re-place the resident stacks for a DIFFERENT mesh in-process
        (the elastic mid-run reshard path): each stack gathers to host
        once and re-places with the batch axis sharded over the new
        ``data`` axis when it tiles (replicated otherwise — placement is
        an optimization, never a semantics change). The stacks' values
        are untouched, so a fused chunk launched after ``respec`` reads
        bit-identical data at the new width."""
        n_shard = _data_shards(mesh)
        sharded = mesh is not None and self.batch % n_shard == 0
        if not sharded:
            n_shard = 1

        def move(a):
            return None if a is None else _place(np.asarray(a), mesh,
                                                 sharded)

        self.features = move(self.features)
        self.labels = move(self.labels)
        self.features_mask = move(self.features_mask)
        self.labels_mask = move(self.labels_mask)
        self.mesh = mesh
        self.n_shard = n_shard
        return self

    @classmethod
    def build(cls, data, budget_mb: Optional[float] = None,
              buckets: Optional[Sequence[int]] = None, mesh=None,
              accum_steps: int = 1) -> Optional["DeviceDataSetCache"]:
        return _traced_build(cls, data, budget_mb, buckets, mesh,
                             accum_steps)

    @classmethod
    def _build(cls, data, budget_mb: Optional[float] = None,
               buckets: Optional[Sequence[int]] = None, mesh=None,
               accum_steps: int = 1) -> Optional["DeviceDataSetCache"]:
        budget = cache_budget_mb() if budget_mb is None else float(budget_mb)
        if budget <= 0:
            return None
        limit = budget * 1024 ** 2
        n_shard = _data_shards(mesh)
        try:
            batches = _drain(data)
        except TypeError:
            return None
        if not batches:
            return None
        if any(getattr(ds, "labels", None) is None for ds in batches):
            return None  # loss needs labels; unsupervised streams stream
        dtype = cache_dtype()
        itemsize = None if dtype is None else np.dtype(dtype).itemsize
        target = 0
        running = 0
        for ds in batches:
            n = int(ds.features.shape[0])
            b = bucket_size(n, buckets)
            target = max(target, b)
            running += (_nbytes_padded(ds.features, b, itemsize)
                        + _nbytes_padded(ds.labels, b, itemsize))
            # optimistic early exit (final per-shard check governs): bail
            # before stacking a dataset that cannot fit even when sharded
            if running / n_shard > limit:
                _reset(data)
                return None
        # bucket batch must tile the data axis to shard; otherwise the
        # stacks replicate over the same mesh (placement is an
        # optimization — never fail the build over it)
        sharded = mesh is not None and target % n_shard == 0
        if not sharded:
            n_shard = 1
        total = 0
        step_bytes = 0
        for ds in batches:
            data_bytes = (_nbytes_padded(ds.features, target, itemsize)
                          + _nbytes_padded(ds.labels, target, itemsize))
            step_bytes = max(step_bytes, data_bytes)
            total += (data_bytes
                      + _nbytes_padded(ds.features_mask, target)
                      + 4 * target * (1 if ds.labels.ndim == 2
                                      else int(ds.labels.shape[1])))
        # Per-chip HBM model (PERF.md §Round-8): the resident stacks divide
        # across the data axis, and the fused scan's live working set — the
        # gathered batch slice plus its gradient-side twin — divides further
        # by the accumulation factor (microbatched inner scan).
        accum = effective_accum_steps(accum_steps, target)
        per_chip = total / n_shard + 2 * step_bytes / (n_shard * accum)
        if per_chip > limit:
            _reset(data)
            return None
        any_fm = any(ds.features_mask is not None for ds in batches)
        try:
            features = _stack_padded([ds.features for ds in batches], target)
            labels = _stack_padded([ds.labels for ds in batches], target)
            fm = None
            if any_fm:
                fm = _stack_padded(
                    [ds.features_mask if ds.features_mask is not None
                     else np.ones(ds.features.shape[:2], np.float32)
                     for ds in batches], target)
            lm = np.stack([_host_label_mask(_host(ds.labels),
                                            ds.labels_mask, target)
                           for ds in batches])
        except ValueError:  # ragged trailing shapes — cannot stack
            _reset(data)
            return None
        if dtype is not None:
            features = features.astype(dtype)
            labels = labels.astype(dtype)
        return cls(_place(features, mesh, sharded),
                   _place(labels, mesh, sharded),
                   None if fm is None else _place(fm, mesh, sharded),
                   _place(lm, mesh, sharded),
                   n_batches=len(batches), batch=target,
                   total_examples=sum(int(ds.features.shape[0])
                                      for ds in batches),
                   nbytes=total, mesh=mesh, n_shard=n_shard)


class DeviceMultiDataSetCache:
    """``DeviceDataSetCache`` for MultiDataSet streams (ComputationGraph):
    per-position tuples of ``[N, B, ...]`` stacks, one device transfer per
    array. DataSet batches are promoted via ``MultiDataSet.from_dataset``."""

    def __init__(self, features: Tuple, labels: Tuple,
                 features_masks: Optional[Tuple], labels_masks: Tuple,
                 n_batches: int, batch: int, total_examples: int,
                 nbytes: int, mesh=None, n_shard: int = 1):
        self.features = features
        self.labels = labels
        self.features_masks = features_masks
        self.labels_masks = labels_masks  # always materialized, per head
        self.n_batches = n_batches
        self.batch = batch
        self.total_examples = total_examples
        self.nbytes = nbytes
        self.mesh = mesh
        self.n_shard = n_shard

    @property
    def stacks(self):
        """Per-position twin of :attr:`DeviceDataSetCache.stacks`."""
        return (self.features, self.labels, self.features_masks,
                self.labels_masks)

    def respec(self, mesh) -> "DeviceMultiDataSetCache":
        """Per-position twin of :meth:`DeviceDataSetCache.respec`."""
        n_shard = _data_shards(mesh)
        sharded = mesh is not None and self.batch % n_shard == 0
        if not sharded:
            n_shard = 1

        def move_tuple(t):
            return None if t is None else tuple(
                _place(np.asarray(a), mesh, sharded) for a in t)

        self.features = move_tuple(self.features)
        self.labels = move_tuple(self.labels)
        self.features_masks = move_tuple(self.features_masks)
        self.labels_masks = move_tuple(self.labels_masks)
        self.mesh = mesh
        self.n_shard = n_shard
        return self

    @classmethod
    def build(cls, data, budget_mb: Optional[float] = None,
              buckets: Optional[Sequence[int]] = None, mesh=None,
              accum_steps: int = 1) -> Optional["DeviceMultiDataSetCache"]:
        return _traced_build(cls, data, budget_mb, buckets, mesh,
                             accum_steps)

    @classmethod
    def _build(cls, data, budget_mb: Optional[float] = None,
               buckets: Optional[Sequence[int]] = None, mesh=None,
               accum_steps: int = 1) -> Optional["DeviceMultiDataSetCache"]:
        from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet

        budget = cache_budget_mb() if budget_mb is None else float(budget_mb)
        if budget <= 0:
            return None
        limit = budget * 1024 ** 2
        n_shard = _data_shards(mesh)
        try:
            batches = _drain(data)
        except TypeError:
            return None
        batches = [MultiDataSet.from_dataset(b) if isinstance(b, DataSet)
                   else b for b in batches]
        if not batches:
            return None
        n_in = len(batches[0].features)
        n_out = len(batches[0].labels)
        if any(len(b.features) != n_in or len(b.labels) != n_out
               or any(l is None for l in b.labels) for b in batches):
            return None
        dtype = cache_dtype()
        itemsize = None if dtype is None else np.dtype(dtype).itemsize
        target = 0
        running = 0
        for mds in batches:
            n = int(mds.features[0].shape[0])
            b = bucket_size(n, buckets)
            target = max(target, b)
            running += sum(_nbytes_padded(a, b, itemsize)
                           for a in list(mds.features) + list(mds.labels))
            if running / n_shard > limit:
                _reset(data)
                return None
        sharded = mesh is not None and target % n_shard == 0
        if not sharded:
            n_shard = 1
        try:
            features = tuple(
                _stack_padded([b.features[i] for b in batches], target)
                for i in range(n_in))
            labels = tuple(
                _stack_padded([b.labels[i] for b in batches], target)
                for i in range(n_out))
            fms = None
            if any(b.features_masks is not None
                   and any(m is not None for m in b.features_masks)
                   for b in batches):
                fms = tuple(
                    _stack_padded(
                        [_mask_or_ones(b, i) for b in batches], target)
                    for i in range(n_in))
            lms = tuple(
                np.stack([
                    _host_label_mask(
                        _host(b.labels[i]),
                        None if b.labels_masks is None else b.labels_masks[i],
                        target)
                    for b in batches])
                for i in range(n_out))
        except ValueError:
            _reset(data)
            return None
        if dtype is not None:
            features = tuple(a.astype(dtype) for a in features)
            labels = tuple(a.astype(dtype) for a in labels)
        nbytes = sum(a.nbytes for a in features + labels + lms)
        if fms is not None:
            nbytes += sum(a.nbytes for a in fms)
        # per-chip model: sharded resident stacks + the accumulated scan's
        # per-step working set (one batch slice + gradient twin, /K)
        step_bytes = sum(a[0].nbytes for a in features + labels)
        accum = effective_accum_steps(accum_steps, target)
        if nbytes / n_shard + 2 * step_bytes / (n_shard * accum) > limit:
            _reset(data)
            return None
        return cls(tuple(_place(a, mesh, sharded) for a in features),
                   tuple(_place(a, mesh, sharded) for a in labels),
                   None if fms is None else tuple(_place(a, mesh, sharded)
                                                  for a in fms),
                   tuple(_place(a, mesh, sharded) for a in lms),
                   n_batches=len(batches), batch=target,
                   total_examples=sum(int(b.features[0].shape[0])
                                      for b in batches),
                   nbytes=nbytes, mesh=mesh, n_shard=n_shard)


def _traced_build(cls, data, budget_mb, buckets, mesh, accum_steps):
    """``cache.build`` span around either cache class's ``_build``: the
    drain + pad + host->device transfer is the fused pipeline's one big
    serial host cost, so its duration (and whether it fell back to
    streaming) belongs on the timeline."""
    from deeplearning4j_tpu.monitor import record_counter, tracer

    with tracer().span("cache.build", kind=cls.__name__) as sp:
        out = cls._build(data, budget_mb=budget_mb, buckets=buckets,
                         mesh=mesh, accum_steps=accum_steps)
        sp.attrs["cached"] = out is not None
        if out is not None:
            sp.attrs.update(n_batches=out.n_batches, batch=out.batch,
                            mb=round(out.nbytes / 1024 ** 2, 3),
                            n_shard=out.n_shard)
    record_counter("cache_builds_total", kind=cls.__name__,
                   outcome="cached" if out is not None else "fallback")
    return out


def chunk_deadline_s(chunk_steps: int, width_factor: float = 1.0) -> float:
    """StepWatchdog deadline for one fused chunk dispatch, scaled by the
    number of fused optimizer steps it contains. ``DL4J_STEP_DEADLINE_S``
    sets the per-step budget exactly (tests use tiny values); unset, a
    generous 30 s/step floored at 120 s — the first dispatch includes the
    chunk program's XLA compile, which can take minutes on its own.

    ``width_factor`` rescales the budget after an elastic reshard: a
    chunk on a mesh shrunk to ``1/f`` of the width the run started at
    legitimately takes up to ``f``× longer per step, and must not be
    flagged as a stall for it. Growth never tightens the deadline
    (``width_factor`` is clamped to >= 1) — a generous deadline is a
    missed detection at worst; a tight one aborts healthy work."""
    raw = os.environ.get("DL4J_STEP_DEADLINE_S", "")
    steps = max(1, int(chunk_steps))
    factor = max(1.0, float(width_factor))
    try:
        if raw:
            return float(raw) * steps * factor
    except ValueError:
        pass
    return max(120.0, 30.0 * steps * factor)


def elastic_reshard(net, cache, mesh) -> None:
    """Chunk-boundary mid-run mesh grow/shrink, in-process.

    The hot-path twin of ``FaultTolerantTrainer.resume(mesh=)``'s
    re-sharding contract, minus the checkpoint round trip: the trainable
    state (params / updater state / net state) snapshots to FULL host
    tensors (GSPMD's sharding is a layout, not a format — a full tensor
    lands on any topology), re-places via the sharding registry on the
    new mesh, and the dataset cache ``respec``s its stacks onto the new
    ``data`` axis. Because the snapshot is topology-free and the
    registry re-derives specs from the NEW mesh, this handles *topology*
    changes, not just width changes: 8x1 -> 4x2 re-shards TP leaves over
    the new ``model`` axis (the collective-redistribution formulation of
    arXiv 2112.01075, realized as gather-to-host + registry re-place).
    Everything else — the epoch RNG key chain, the iteration count, the
    LR scale, the chunk cursor — is host state the driver carries and is
    untouched, so the continued run consumes the identical key stream
    and visits the identical batches: final params match the
    uninterrupted run to <= 1e-6 (the gradient all-reduce's summation
    order is the only difference across widths).

    ``mesh=None`` re-places on the default single device (shrink to one
    chip)."""
    import jax

    params = jax.device_get(net.params)
    upd = jax.device_get(net.updater_state)
    nst = jax.device_get(net.net_state)
    if mesh is None:
        net.params = jax.device_put(params)
        net.updater_state = jax.device_put(upd)
        net.net_state = jax.device_put(nst)
    else:
        net.params, net.updater_state, net.net_state = params, upd, nst
        if hasattr(net, "_place_on_mesh"):
            net._place_on_mesh(mesh)
        else:
            net._place_replicated(mesh)
    # drop cached fused programs: the flat-vs-per-layer updater-apply
    # choice is baked in at TRACE time from the live placements, and a
    # topology change (e.g. 8x1 -> 4x2) can flip it — a stale trace
    # would miscompile under the new shardings (the wrapper's
    # _apply_reshard already does this for its own program cache)
    steps = getattr(net, "_epoch_steps", None)
    if steps is not None:
        steps.clear()
    cache.respec(mesh)


def drive_epoch_chunks(net, cache, num_epochs: int,
                       chunk_epochs: Optional[int], launch_chunk, *,
                       shuffle: bool = True, guard: str = "off",
                       replay_step=None, on_chunk=None, reshard=None):
    """The shared host-side chunk driver behind both classes' fit_epochs:
    splits the net's RNG into per-chunk epoch keys, launches each fused
    chunk (``launch_chunk(epoch_keys) -> ([k, N] hist, [k, N] trips or
    None, [k, N, 4] metrics or None)`` updates the net's params/updater/
    net state itself), advances the iteration count by k*N, and fires
    listeners once per chunk — the host decision point. Default chunking:
    whole run without listeners, one epoch with them. Returns the
    concatenated ``[E, N]`` loss history.

    Telemetry (the observability bus around the fast path): the whole
    run is one ``epoch.run`` tracer span (key splits, dispatches,
    listeners, readbacks, the history's concatenation — not the caller's
    wait for the history); every chunk
    dispatch runs inside an ``epoch.chunk`` tracer span (and bumps the
    ``train_chunk_dispatches_total`` counter); per-chunk host readbacks
    get ``epoch.readback`` spans; the metrics-pack history (when the
    chunk program carries one) accumulates device-side — zero extra
    syncs — and lands in ``net._last_metrics`` as ``[E, N, 4]`` at end
    of run. Listeners implementing ``chunk_done(model, iteration0,
    losses, metrics=)`` receive each chunk's DEVICE histories with the
    chunk's global starting iteration (correct numbering across chunks
    and resume); listeners without it keep the legacy once-per-chunk
    ``iteration_done`` firing.

    Self-healing hooks (the robustness layer around the fast path):

    - every chunk dispatch runs under a :class:`StepWatchdog` whose
      deadline scales with the chunk's step count (``chunk_deadline_s``)
      — a hung XLA dispatch is logged as a stall, not a silent wedge —
      and declares the ``epoch.chunk`` fault site for chaos tests;
    - ``guard`` is the resolved ``DL4J_NAN_GUARD`` policy. When the
      chunk program carries the numeric sentinel (``trips`` not None)
      the full boolean history lands in ``net._last_sentinel``
      (``[E, N]``, True = tripped/skipped step) and trips are enforced
      via ``_enforce_nan_guard`` (log / halve ``net._lr_scale_host`` /
      replay-localize + raise ``TrainingDivergedError``). ``halve_lr``
      and ``raise`` must act between chunks, so they read the history
      per chunk — one host sync each, blocking on that chunk's
      completion; ``skip`` takes no per-chunk action, so its read (and
      its warning) defers to end-of-run and chunk dispatches stay
      pipelined exactly like the unguarded path. Under ``raise`` the
      state is snapshotted before each launch (the chunk program
      donates its inputs) so ``replay_step(params, upd, nst, iteration,
      batch_index, rng) -> (params, upd, nst, loss)`` can re-run the
      chunk per-step from the last-good state;
    - ``on_chunk(epochs_done) -> bool`` fires after listeners;
      returning True stops the run at this chunk boundary (the
      preemption-safe checkpoint hook — ``FaultTolerantTrainer`` sets
      the absolute epoch cursor, saves, and polls its
      ``PreemptionGuard`` here);
    - elastic reshard: a pending ``net.request_reshard(mesh)`` request
      is honored at the NEXT chunk boundary via the ``reshard(mesh)``
      callback (both network classes pass ``elastic_reshard``): device
      snapshot → respec → continue inside a ``reshard.elastic`` span
      (the ledger books it as ``reshard`` badput), with the watchdog
      deadline recomputed from the new chunk shape/device width. Fit
      paths that pin per-mesh programs (``ParallelWrapper``) pass no
      callback; a request there is logged and dropped, never applied
      unsafely.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.monitor import record_counter, tracer
    from deeplearning4j_tpu.monitor.ledger import (
        ledger_chunk_done,
        ledger_chunk_start,
        ledger_run_end,
        ledger_run_start,
    )
    from deeplearning4j_tpu.resilience import faults
    from deeplearning4j_tpu.resilience.watchdog import StepWatchdog

    from deeplearning4j_tpu.monitor.profile import profile_enabled

    if chunk_epochs is None:
        chunk_epochs = 1 if net.listeners else num_epochs
    chunk_epochs = max(1, min(int(chunk_epochs), num_epochs))
    model_name = type(net).__name__
    history = []
    sentinel_chunks = []
    metrics_chunks = []
    net._last_sentinel = None
    net._last_metrics = None
    # HBM watermarks sample ONLY at chunk boundaries (host-side, after
    # the dispatch) and only under DL4J_PROFILE — the default path never
    # pays the memory_stats/live-array walk
    profiling = profile_enabled()
    net._hbm_watermarks = [] if profiling else None
    # skip takes no per-chunk action — keep its trip reads off the hot
    # path (device arrays accumulate; one sync at end of run)
    defer_inspect = guard not in ("halve_lr", "raise")
    done = 0
    stopped = False
    run_error = None
    # the width the deadline budget is calibrated at: a later shrink to
    # 1/f of it rescales the watchdog deadline by f (satellite contract:
    # a legitimate post-shrink chunk is slower, not stalled)
    base_shard = max(1, cache.n_shard)
    watchdog = StepWatchdog(
        chunk_deadline_s(chunk_epochs * cache.n_batches))
    net._chunk_watchdog = watchdog  # introspection (tests, metrics)
    # the whole run under ONE program span: every device program the
    # driver launches (the chunk program, the eager key split and score
    # read beside it) and every idle nanosecond between them lies inside
    # a ``dl4j.epoch.run`` event of a device trace. The wait for the loss
    # history is the caller's and stays outside
    run_attrs = dict(model=model_name, epochs=num_epochs,
                     steps=num_epochs * cache.n_batches,
                     chunk_epochs=chunk_epochs, guard=guard)
    with tracer().span("epoch.run", **run_attrs):
        # the run-ledger window opens here and closes in the finally
        # below: the ledger (and the flight recorder, when DL4J_FLIGHT is
        # on) only ever hears from this driver at chunk boundaries — never
        # from inside a traced program (dl4j-lint's host-sync rule)
        ledger_run_start(**run_attrs)
        try:
            with watchdog:
                while done < num_epochs:
                    pending = getattr(net, "_pending_mesh", None)
                    if pending is not None:
                        net._pending_mesh = None
                        new_mesh = pending[0]
                        if reshard is None:
                            logging.getLogger(__name__).warning(
                                "elastic reshard requested but this fit "
                                "path pins per-mesh programs; request "
                                "dropped (use the plain fit_epochs path)")
                        else:
                            with tracer().span("reshard.elastic",
                                               model=model_name,
                                               epoch0=done) as rs:
                                reshard(new_mesh)
                                rs.attrs["n_shard"] = cache.n_shard
                            record_counter("elastic_reshards_total",
                                           model=model_name)
                            watchdog.set_deadline(chunk_deadline_s(
                                chunk_epochs * cache.n_batches,
                                base_shard / max(1, cache.n_shard)))
                    k = min(chunk_epochs, num_epochs - done)
                    faults.fault_point("epoch.chunk")
                    keys = jax.random.split(net._rng, k + 1)
                    net._rng = keys[0]
                    snapshot = None
                    it0 = net.iteration_count
                    if guard == "raise":
                        # launch donates params/updater/net state; keep the
                        # last-good copy so a trip can be replayed per-step
                        snapshot = tuple(
                            jax.tree_util.tree_map(jnp.copy, t)
                            for t in (net.params, net.updater_state,
                                      net.net_state))
                    # the span times the HOST-side dispatch (the XLA launch
                    # returns before the chunk completes; completion shows up
                    # in the next blocking read's epoch.readback span)
                    ledger_chunk_start(model=model_name, epoch0=done,
                                       epochs=k)
                    with tracer().span("epoch.chunk", model=model_name,
                                       epochs=k,
                                       steps=k * cache.n_batches,
                                       epoch0=done):
                        hist, trips, mets = launch_chunk(keys[1:])
                    watchdog.beat()
                    ledger_chunk_done(model=model_name, epoch0=done,
                                      epochs=k)
                    net._train_dispatches += 1
                    record_counter("train_chunk_dispatches_total",
                                   model=model_name)
                    if profiling:
                        from deeplearning4j_tpu.monitor.memory import (
                            sample_hbm_watermark)

                        net._hbm_watermarks.append(
                            sample_hbm_watermark(tag="epoch.chunk"))
                    net.iteration_count += k * cache.n_batches
                    net._score = hist[-1, -1]  # device scalar
                    if mets is not None:
                        metrics_chunks.append(mets)  # device; no sync
                    if trips is not None:
                        if defer_inspect:
                            sentinel_chunks.append(trips)  # device; no sync
                        else:
                            # halve_lr/raise act between chunks: this read
                            # blocks on the chunk's completion — the one
                            # host sync those policies cost per chunk
                            with tracer().span("epoch.readback",
                                               what="sentinel"):
                                t = np.asarray(trips)
                            sentinel_chunks.append(t)
                            if t.any():
                                _enforce_nan_guard(net, guard, t, done,
                                                   keys[1:], shuffle,
                                                   cache.n_batches, snapshot,
                                                   it0, replay_step)
                    history.append(hist)
                    done += k
                    for listener in net.listeners:
                        chunk_cb = getattr(listener, "chunk_done", None)
                        if chunk_cb is not None:
                            chunk_cb(net, it0, hist, metrics=mets)
                        else:  # pre-telemetry listener protocol
                            listener.iteration_done(net, net.iteration_count)
                    if on_chunk is not None and on_chunk(done):
                        stopped = True
                        break
        except BaseException as e:
            run_error = e
            raise
        finally:
            # flush even when the raise policy aborts the run mid-chunk: a
            # TrainingDivergedError handler reads the history that tripped it
            if metrics_chunks:
                net._last_metrics = _concat_chunks(metrics_chunks)
            if sentinel_chunks:
                with tracer().span("epoch.readback", what="sentinel_flush"):
                    full = np.concatenate([np.asarray(t)
                                           for t in sentinel_chunks])
                net._last_sentinel = full
                if defer_inspect and full.any():
                    # the deferred skip-policy report (epoch indices are
                    # absolute: the history covers the run from epoch 0)
                    _enforce_nan_guard(net, guard, full, 0, None, shuffle,
                                       cache.n_batches, None, 0, None)
            # close the ledger window LAST so the sentinel flush above is
            # still inside the run it belongs to; the status string is what
            # flight_report classifies a dead run's sibling from
            ledger_run_end(
                status=(f"error:{type(run_error).__name__}"
                        if run_error is not None
                        else ("stopped" if stopped else "clean")),
                model=model_name, epochs_done=done)
        return _concat_chunks(history)


def _concat_chunks(chunks):
    """Concatenate per-chunk device arrays along axis 0. Chunks from a
    run that resharded mid-way can be COMMITTED to different device
    sets (programs with pinned out_shardings, e.g. ParallelWrapper's);
    jnp.concatenate refuses mixed placements, so those gather to host
    once and concatenate there — the caller is about to read the
    history anyway."""
    import jax.numpy as jnp

    if len(chunks) == 1:
        return chunks[0]
    try:
        return jnp.concatenate(chunks)
    except ValueError:
        return jnp.asarray(np.concatenate(
            [np.asarray(c) for c in chunks]))


def _enforce_nan_guard(net, policy: str, trips: np.ndarray,
                       done_epochs: int, chunk_keys, shuffle: bool,
                       n_batches: int, snapshot, it0: int,
                       replay_step) -> None:
    """Host-side policy for a chunk whose sentinel tripped. ``trips`` is
    the chunk's ``[k, N]`` boolean history (True = the in-program guard
    skipped that step)."""
    from deeplearning4j_tpu.resilience.guard import TrainingDivergedError

    log = logging.getLogger(__name__)
    n_trips = int(trips.sum())
    e_rel, step = (int(v) for v in np.argwhere(trips)[0])
    epoch = done_epochs + e_rel
    if policy == "halve_lr":
        net._lr_scale_host = getattr(net, "_lr_scale_host", 1.0) * 0.5
        log.warning(
            "numeric sentinel: %d non-finite step(s) skipped in-program "
            "(first at epoch %d, step %d); halving host LR scale to %g "
            "[DL4J_NAN_GUARD=halve_lr]", n_trips, epoch, step,
            net._lr_scale_host)
        return
    if policy != "raise":
        log.warning(
            "numeric sentinel: %d non-finite step(s) skipped in-program "
            "(first at epoch %d, step %d); params/updater state carried "
            "unchanged through them [DL4J_NAN_GUARD=skip]", n_trips,
            epoch, step)
        return
    batch_index = loss = None
    if replay_step is not None and snapshot is not None:
        batch_index, loss = _replay_localize(
            replay_step, snapshot, chunk_keys, shuffle, n_batches,
            e_rel, step, it0)
    raise TrainingDivergedError(epoch=epoch, step=step,
                                batch_index=batch_index, loss=loss,
                                n_trips=n_trips)


def _replay_localize(replay_step, snapshot, chunk_keys, shuffle: bool,
                     n_batches: int, e_trip: int, s_trip: int, it0: int):
    """Per-step replay from the chunk-start snapshot up to (and through)
    the first tripped step, re-deriving each epoch's batch order and step
    keys EAGERLY from the same pure ``epoch_schedule`` derivation the
    fused program traced — so the replay consumes the identical RNG
    stream and visits the identical batches. Returns ``(batch_index,
    loss)`` of the offending step: the index into the dataset's batch
    list (the permutation inverts host-side for free) and the non-finite
    loss that tripped the sentinel."""
    params, upd, nst = snapshot
    it = it0
    order = None
    loss = None
    for e in range(e_trip + 1):
        order, step_keys = epoch_schedule(chunk_keys[e], n_batches,
                                          shuffle)
        order = np.asarray(order)
        last = s_trip if e == e_trip else n_batches - 1
        for j in range(last + 1):
            params, upd, nst, loss = replay_step(
                params, upd, nst, it, int(order[j]), step_keys[j])
            it += 1
    return int(order[s_trip]), float(loss)


def stream_epochs(net, data, num_epochs: int) -> None:
    """Over-budget fallback shared by both classes: per-step fit with the
    host->device link hidden behind an N-deep async device-prefetch
    buffer (``DL4J_PREFETCH_DEPTH``)."""
    from deeplearning4j_tpu.datasets.iterator import (
        AsyncDataSetIterator, DataSetIterator)

    stream = data
    if (isinstance(data, DataSetIterator)
            and not isinstance(data, AsyncDataSetIterator)):
        stream = AsyncDataSetIterator(
            data, queue_size=prefetch_depth(), device_prefetch=True)
    for _ in range(num_epochs):
        net.fit(stream)


def _mask_or_ones(mds, i):
    m = None if mds.features_masks is None else mds.features_masks[i]
    if m is not None:
        return m
    f = mds.features[i]
    shape = f.shape[:2] if np.ndim(f) == 3 else (f.shape[0], 1)
    return np.ones(shape, np.float32)


def _reset(data) -> None:
    """Hand a partially/fully drained iterator back ready for streaming."""
    if hasattr(data, "reset"):
        data.reset()
