"""Ulysses sequence parallelism: all-to-all head↔sequence resharding.

The second of the two long-context strategies SURVEY §7.7d calls for
(alongside ``ring_attention``): DeepSpeed-Ulysses-style context parallelism.
Inputs arrive sharded on the SEQUENCE axis ([B, T/P, H, D] per device); an
``all_to_all`` over the sequence axis re-shards to head parallelism
([B, T, H/P, D] — every device sees the FULL sequence for its subset of
heads), plain softmax attention runs locally with no communication inside
the kernel, and a second all-to-all restores sequence sharding. Two
collectives per attention call versus ring attention's P permutes — the
better trade when heads ≥ devices and ICI all-to-all bandwidth is plentiful
(the scaling-book recipe); ring attention wins when T is huge and overlap
matters. Both ride the same mesh axes, so callers can switch per layer.

No counterpart exists in the reference (pre-attention codebase, SURVEY §5
"long-context: absent") — this is greenfield capability the TPU build is
required to provide.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.ops.attention import NEG_INF, causal_band_mask
from deeplearning4j_tpu.parallel.mesh import SEQUENCE_AXIS


def _local_attention(q, k, v, *, causal: bool, t_offset_q=0, window=None):
    """Plain softmax attention on full-sequence blocks [B, T, h, D].
    ``window`` (requires causal) keeps k in ``(q - window, q]`` via the
    shared ``ops.attention.causal_band_mask``."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    if causal:
        mask = causal_band_mask(q.shape[1], k.shape[1], window=window,
                                q_offset=t_offset_q)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, mesh: Mesh, causal: bool = False,
                      axis_name: str = SEQUENCE_AXIS, window=None):
    """Self-attention over sequence-sharded [B, T, H, D] inputs.

    ``H`` must be divisible by the sequence-axis size (each device owns
    H/P heads during the compute phase). ``window`` (requires causal)
    applies sliding-window masking inside the local full-sequence
    attention — the all-to-alls are unchanged.
    """
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    if axis_name not in mesh.shape or mesh.shape[axis_name] == 1:
        return _local_attention(q, k, v, causal=causal, window=window)
    n_seq = mesh.shape[axis_name]
    if q.shape[2] % n_seq:
        raise ValueError(
            f"num_heads {q.shape[2]} not divisible by sequence-parallel "
            f"degree {n_seq}")

    def body(q_blk, k_blk, v_blk):
        # [B, T/P, H, D] → all-to-all → [B, T, H/P, D]: split the head
        # axis across devices, concatenate the sequence axis
        def seq_to_head(x):
            return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

        def head_to_seq(x):
            return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

        qh = seq_to_head(q_blk)
        kh = seq_to_head(k_blk)
        vh = seq_to_head(v_blk)
        out = _local_attention(qh, kh, vh, causal=causal, window=window)
        return head_to_seq(out)

    spec = P(None, axis_name, None, None)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ulysses_self_attention_sharded(mesh: Mesh):
    """Convenience: jitted fn(q, k, v, causal) bound to ``mesh`` (mirrors
    ``ring_self_attention_sharded``)."""

    @functools.partial(jax.jit, static_argnames=("causal",))
    def fn(q, k, v, causal=False):
        return ulysses_attention(q, k, v, mesh, causal=causal)

    return fn
