"""Shape bucketing + padding for the inference/eval path.

A fresh XLA compile costs seconds, so a stream of ragged batch sizes — the
tail of every epoch, user-sized ``output()`` calls, variable serving
traffic — turns into a compile per distinct shape. Padding the batch axis up a geometric ladder bounds the
number of compiled programs at the ladder length while wasting at most 2x
compute on the padded rows (row-independent inference ops make pad rows
inert; reductions mask them out).

This generalizes ``nlp/trees.pad_to_bucket`` (tree-size buckets for the
RNTN) to whole DataSet batches: features/labels pad with zeros, and the
label mask is created-or-extended with zeros so pad rows contribute nothing
to any mask-weighted reduction (loss, confusion counts, regression sums).
The time axis of RNN batches is NOT bucketed — bidirectional layers read
future timesteps, so time padding is not inert there; time raggedness
should be handled upstream (fixed-length windows / TBPTT). The one
sanctioned exception is the serving prefill's PROMPT axis (causal
decoder, pad tail causally unreachable): ``prompt_bucket``/``pad_prompt``
below, consumed only by ``deeplearning4j_tpu/serving/``.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import numpy as np

# Powers of two: ragged sizes share at most log2(max/min) programs, and any
# pad waste is < 2x. Sizes beyond the ladder round up to a multiple of the
# top rung (still a bounded program count for huge batches).
DEFAULT_BATCH_BUCKETS: Tuple[int, ...] = (
    1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def bucketing_enabled() -> bool:
    """Kill switch: ``DL4J_DISABLE_BUCKETING=1`` makes every bucket exact
    (one compile per shape, reference behavior) — an escape hatch for
    debugging numerical diffs down to the padded program."""
    return os.environ.get("DL4J_DISABLE_BUCKETING", "") != "1"


def bucket_size(n: int, buckets: Optional[Sequence[int]] = None) -> int:
    """Smallest ladder rung >= n (n itself when bucketing is disabled)."""
    if n <= 0 or not bucketing_enabled():
        return n
    for b in (buckets or DEFAULT_BATCH_BUCKETS):
        if n <= b:
            return int(b)
    top = int((buckets or DEFAULT_BATCH_BUCKETS)[-1])
    return ((n + top - 1) // top) * top


def pad_axis0(a, target: int):
    """Zero-pad the batch axis up to ``target`` rows (numpy or jax array,
    padded with the matching library so device arrays stay on device)."""
    if a is None:
        return None
    n = int(a.shape[0])
    if n >= target:
        return a
    widths = [(0, target - n)] + [(0, 0)] * (a.ndim - 1)
    if isinstance(a, np.ndarray):
        return np.pad(a, widths)
    import jax.numpy as jnp

    return jnp.pad(a, widths)


def padded_label_mask(labels, labels_mask, target: int):
    """The label mask that makes pad rows inert: the existing mask (or ones
    when absent) extended with ZEROS to ``target`` rows. Shape follows the
    labels: [b] for [b, c] labels, [b, t] for [b, t, c] (RNN label masks
    compose — a masked timestep stays masked, a pad row is fully masked)."""
    import jax.numpy as jnp

    b = int(labels.shape[0])
    if labels_mask is None:
        shape = (b,) if labels.ndim == 2 else (b, int(labels.shape[1]))
        labels_mask = jnp.ones(shape, jnp.float32)
    else:
        labels_mask = jnp.asarray(labels_mask, jnp.float32)
    return pad_axis0(labels_mask, target)


# ---------------------------------------------------------------------------
# Prompt-length ladder (serving only).
#
# The "time axis is never bucketed" rule above is about TRAINING/EVAL
# batches: bidirectional layers read future timesteps, so time padding is
# not inert there. A causal decoder prefill is different — position i
# attends keys 0..i only, so tokens appended PAST the prompt can never
# influence the real positions, and the serving layer pads every prompt up
# a powers-of-two ladder to bound prefill compiles the same way the batch
# axis is bounded. Decode masks keys strictly beyond the write cursor, so
# the pad tail in the KV pool is never attended either (mask correctness
# is asserted in tests/test_serving.py).
DEFAULT_PROMPT_BUCKETS: Tuple[int, ...] = (
    16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def prompt_bucket(n: int, buckets: Optional[Sequence[int]] = None,
                  max_len: Optional[int] = None) -> int:
    """Smallest prompt-ladder rung >= ``n`` for the serving prefill.

    ``max_len`` (the server's slot capacity T_max) caps the rung — a
    prompt longer than every rung below the cap pads only to ``max_len``
    (never past the KV pool). ``DL4J_DISABLE_BUCKETING=1`` makes every
    prompt exact, the same escape hatch as the batch ladder."""
    if n <= 0:
        raise ValueError(f"prompt length must be >= 1 (got {n})")
    if max_len is not None and n > max_len:
        raise ValueError(f"prompt length {n} exceeds max_len={max_len}")
    if not bucketing_enabled():
        return n
    b = bucket_size(n, buckets or DEFAULT_PROMPT_BUCKETS)
    return b if max_len is None else min(b, max_len)


def pad_prompt(tokens, bucket: int, pad_id: int = 0):
    """Right-pad token rows ([t] or [b, t] int) to ``bucket`` positions.

    Returns ``(padded, length)`` with ``length`` the real prompt length
    — the prefill reads its last hidden state from ``length - 1`` and
    starts the slot's write cursor there, so the pad tail is causally
    unreachable (pad tokens sit at positions the decode mask excludes
    until they are overwritten by generated tokens)."""
    a = np.asarray(tokens)
    t = int(a.shape[-1])
    if t > bucket:
        raise ValueError(f"prompt length {t} exceeds bucket {bucket}")
    widths = [(0, 0)] * (a.ndim - 1) + [(0, bucket - t)]
    return np.pad(a, widths, constant_values=pad_id), t


def pad_dataset(ds, buckets: Optional[Sequence[int]] = None):
    """Pad a DataSet's batch axis to its bucket, mask-correctly.

    Features/labels pad with zeros; the labels mask is ALWAYS present on
    the result (created as ones when absent) so a mixed stream of full and
    ragged batches still compiles ONE program per bucket — a mask-less full
    batch and a masked tail would otherwise be two distinct jit signatures
    at the same shape. The features mask pads only when already present
    (synthesizing one would change RNN forward semantics for unmasked
    callers)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    n = int(ds.features.shape[0])
    b = bucket_size(n, buckets)
    labels = ds.labels
    if labels is None:
        return DataSet(pad_axis0(ds.features, b), None,
                       pad_axis0(ds.features_mask, b), None)
    lm = padded_label_mask(labels, ds.labels_mask, b)
    return DataSet(pad_axis0(ds.features, b), pad_axis0(labels, b),
                   pad_axis0(ds.features_mask, b), lm)
