"""Ring attention: context parallelism over a ``sequence`` mesh axis.

Long-context mechanism (Liu et al., "Ring Attention with Blockwise
Transformers") — greenfield relative to the reference, whose only
long-sequence tool was truncated BPTT (SURVEY §5). The sequence axis is
sharded across devices; each device keeps its Q block resident and K/V
blocks rotate around the ring via ``ppermute`` over ICI, overlapping the
collective with the local blockwise attention. Softmax is computed online
(flash-style running max/normalizer), so the full [t, t] score matrix never
materializes and sequence length scales linearly with the number of devices.

Implementation: ``shard_map`` over the mesh; the per-device body is a
``lax.fori_loop`` over ring steps with carry (o, m, l, k, v).

Two per-block implementations:

- ``impl="xla"`` — blockwise jnp math, XLA-fused (default; differentiable
  by plain autodiff).
- ``impl="flash"`` — the Pallas flash kernel (pallas/flash_attention.py)
  runs each (local q, visiting k/v) block, and blocks merge via their
  log-sum-exp; a ring-level ``custom_vjp`` implements the matching
  backward as a second ring pass (each block's gradient contribution is
  independent given the merged lse, so dk/dv accumulators travel around
  the ring with their k/v blocks).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops.attention import NEG_INF, causal_band_mask
from deeplearning4j_tpu.parallel.mesh import SEQUENCE_AXIS


def _block_attn(q, k, v, q_offset, k_offset, *, causal, scale,
                window=None):
    """Blockwise attention logits for absolute positions; returns
    (scores·v contribution, running-max, normalizer pieces). ``window``
    (requires causal) keeps k in ``(q - window, q]`` — same sliding-window
    convention as ``ops.attention``."""
    # q: [b, tq, h, d]; k/v: [b, tk, h, d]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        keep = causal_band_mask(q.shape[1], k.shape[1], window=window,
                                q_offset=q_offset, k_offset=k_offset)
        logits = jnp.where(keep, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)  # [b, h, tq]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)  # [b, h, tq]
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return pv, m, l


class _StaticConfig:
    """Base for hashable static-config objects passed as custom_vjp
    nondiff args: identity is (concrete type, slot values)."""

    __slots__ = ()

    def _key(self):
        return tuple(getattr(self, s) for s in type(self).__slots__)

    def __hash__(self):
        return hash((type(self).__name__, self._key()))

    def __eq__(self, other):
        return type(other) is type(self) and other._key() == self._key()


def _lse_merge(o, lse, o_blk, lse_blk):
    """Merge a new normalized attention block into the running (o, lse)
    accumulator via log-sum-exp: the ONE implementation both flash rings
    share. o accumulates in f32; fully-masked blocks carry lse = -inf-ish
    and underflow to zero weight."""
    lse_new = jnp.logaddexp(lse, lse_blk)
    w_old = jnp.exp(lse - lse_new)
    w_new = jnp.exp(lse_blk - lse_new)
    o_new = (o * jnp.swapaxes(w_old, 1, 2)[..., None]
             + o_blk.astype(jnp.float32)
             * jnp.swapaxes(w_new, 1, 2)[..., None])
    return o_new, lse_new


class _RingFlashConfig(_StaticConfig):
    """Hashable statics for the ring-level custom_vjp."""

    __slots__ = ("causal", "scale", "n_ring", "axis_name", "interpret")

    def __init__(self, causal, scale, n_ring, axis_name, interpret):
        self.causal = causal
        self.scale = scale
        self.n_ring = n_ring
        self.axis_name = axis_name
        self.interpret = interpret


def _ring_flash_fwd_impl(cfg, q_blk, k_blk, v_blk):
    """Forward ring pass with the Pallas kernel per block. Per-device
    shards [b, t_local, h, d] → (out, lse [b, h, t_local])."""
    from deeplearning4j_tpu.pallas.flash_attention import (
        MASK_VALUE, flash_attention_fwd)

    n = cfg.n_ring
    axis = cfg.axis_name
    my_idx = lax.axis_index(axis)
    b, tq, h, d = q_blk.shape
    perm = [(i, (i - 1) % n) for i in range(n)]
    o0 = jnp.zeros((b, tq, h, d), jnp.float32)
    lse0 = jnp.full((b, h, tq), MASK_VALUE, jnp.float32)

    def block(kc, vc, causal_mode):
        def full(_):
            return flash_attention_fwd(
                q_blk, kc, vc, causal=False, scale=cfg.scale,
                interpret=cfg.interpret)

        def diag(_):
            # same-owner block: relative positions align, plain causal
            return flash_attention_fwd(
                q_blk, kc, vc, causal=True, scale=cfg.scale,
                interpret=cfg.interpret)

        def skip(_):
            return (jnp.zeros((b, tq, h, d), q_blk.dtype),
                    jnp.full((b, h, tq), MASK_VALUE, jnp.float32))

        if not cfg.causal:
            return full(None)
        return lax.switch(causal_mode, [full, diag, skip], None)

    def step(s, carry):
        o, lse, kc, vc = carry
        k_owner = (my_idx + s) % n
        causal_mode = jnp.where(k_owner < my_idx, 0,
                                jnp.where(k_owner == my_idx, 1, 2))
        o_blk, lse_blk = block(kc, vc, causal_mode)
        o, lse = _lse_merge(o, lse, o_blk, lse_blk)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        return (o, lse, kc, vc)

    o, lse, _, _ = lax.fori_loop(0, n, step, (o0, lse0, k_blk, v_blk))
    return o.astype(q_blk.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_flash(cfg, q_blk, k_blk, v_blk):
    out, _ = _ring_flash_fwd_impl(cfg, q_blk, k_blk, v_blk)
    return out


def _ring_flash_fwd_rule(cfg, q_blk, k_blk, v_blk):
    out, lse = _ring_flash_fwd_impl(cfg, q_blk, k_blk, v_blk)
    return out, (q_blk, k_blk, v_blk, out, lse)


def _ring_flash_bwd_rule(cfg, res, do):
    """Second ring pass: dq accumulates locally; (dk, dv) accumulators
    travel with their k/v blocks and arrive home after n rotations.
    Per-block gradients run through the Pallas backward kernels (score
    tiles stay in VMEM); blocks never need position offsets because the
    ring visits each block as full (below diagonal), diag (aligned
    spans), or skip."""
    from deeplearning4j_tpu.pallas.flash_attention import (
        flash_backward_pallas)

    q_blk, k_blk, v_blk, out, lse = res
    n = cfg.n_ring
    axis = cfg.axis_name
    my_idx = lax.axis_index(axis)
    b, tq, h, d = q_blk.shape
    perm = [(i, (i - 1) % n) for i in range(n)]

    def block_grads(kc, vc, causal_mode):
        def run(causal):
            return flash_backward_pallas(q_blk, kc, vc, out, lse, do,
                                         causal=causal, scale=cfg.scale,
                                         interpret=cfg.interpret)

        def full(_):
            return run(False)

        def diag(_):
            return run(True)

        def skip(_):
            return (jnp.zeros((b, tq, h, d), jnp.float32),
                    jnp.zeros_like(kc, jnp.float32),
                    jnp.zeros_like(vc, jnp.float32))

        if not cfg.causal:
            return full(None)
        return lax.switch(causal_mode, [full, diag, skip], None)

    def step(s, carry):
        dq, kc, vc, dkc, dvc = carry
        k_owner = (my_idx + s) % n
        causal_mode = jnp.where(k_owner < my_idx, 0,
                                jnp.where(k_owner == my_idx, 1, 2))
        dq_c, dk_c, dv_c = block_grads(kc, vc, causal_mode)
        dq = dq + dq_c
        dkc = dkc + dk_c
        dvc = dvc + dv_c
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        dkc = lax.ppermute(dkc, axis, perm)
        dvc = lax.ppermute(dvc, axis, perm)
        return (dq, kc, vc, dkc, dvc)

    dq0 = jnp.zeros((b, tq, h, d), jnp.float32)
    dq, _, _, dk, dv = lax.fori_loop(
        0, n, step,
        (dq0, k_blk, v_blk, jnp.zeros_like(k_blk, shape=k_blk.shape,
                                           dtype=jnp.float32),
         jnp.zeros_like(v_blk, dtype=jnp.float32)))
    return (dq.astype(q_blk.dtype), dk.astype(k_blk.dtype),
            dv.astype(v_blk.dtype))


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    axis_name: str = SEQUENCE_AXIS,
    impl: str = "xla",
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Ring attention over ``axis_name``. q/k/v: [b, t, h, d] GLOBAL arrays
    (sharded or shardable on the time axis); returns [b, t, h, d] sharded the
    same way. Requires t % mesh.shape[axis_name] == 0.

    ``impl="flash"`` runs each block through the Pallas flash kernel with a
    ring-level custom VJP; ``"xla"`` (default) uses fused jnp blockwise math.

    ``window`` (requires ``causal=True``) composes sliding-window attention
    with the ring: each q block's band ``(q - window, q]`` intersects at most
    ``ceil((window-1)/t_local) + 1`` owner blocks, so the ring runs only that
    many hops — rotating AGAINST the causal direction so the needed
    previous-neighbor blocks arrive first and the loop stops as soon as the
    band is covered (a windowed ring is strictly cheaper than a full ring).
    With ``impl="flash"`` the hop loop is unrolled, which makes each hop's
    q↔k offset static: the diagonal hop runs the causal BANDED Pallas
    kernel, fully-in-band hops run the unmasked kernel, and only the ≤2
    band-edge hops use blockwise XLA math (``_win_ring_flash`` custom_vjp
    mirrors the same trichotomy in the backward ring pass).
    """
    if impl not in ("xla", "flash"):
        raise ValueError(f"unknown ring attention impl {impl!r}")
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    if window is not None and window >= q.shape[1]:
        # a band at least as long as the sequence IS plain causal
        # attention — take the rolled full-ring path instead of unrolling
        # n_ring identical "full" hops
        window = None
    d = q.shape[-1]
    scale_val = scale if scale is not None else float(1.0 / (d ** 0.5))
    if axis_name not in mesh.shape:
        # size-1 sequence axis is dropped from the mesh: no ring, plain
        # single-device attention
        if impl == "flash":
            from deeplearning4j_tpu.pallas.flash_attention import (
                flash_attention)

            return flash_attention(q, k, v, causal=causal, scale=scale_val,
                                   window=window, interpret=interpret)
        pv, m, l = _block_attn(q, k, v, 0, 0, causal=causal, scale=scale_val,
                               window=window)
        denom = jnp.maximum(jnp.swapaxes(l, 1, 2)[..., None], 1e-30)
        return (pv.astype(jnp.float32) / denom).astype(q.dtype)
    n_ring = mesh.shape[axis_name]
    t_local = q.shape[1] // n_ring

    if window is not None:
        if impl == "flash":
            return _windowed_ring_flash(
                q, k, v, mesh, axis_name=axis_name, scale=scale_val,
                window=window, n_ring=n_ring, t_local=t_local,
                interpret=interpret)
        return _windowed_ring(q, k, v, mesh, axis_name=axis_name,
                              scale=scale_val, window=window,
                              n_ring=n_ring, t_local=t_local)

    if impl == "flash":
        cfg = _RingFlashConfig(causal, scale_val, n_ring, axis_name,
                               interpret)
        spec = P(None, axis_name, None, None)
        return shard_map(
            functools.partial(_ring_flash, cfg), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    def body(q_blk, k_blk, v_blk):
        # q_blk/k_blk/v_blk: [b, t_local, h, d] — this device's shard
        my_idx = lax.axis_index(axis_name)
        b, tq, h, dd = q_blk.shape
        o = jnp.zeros((b, tq, h, dd), jnp.float32)
        m = jnp.full((b, h, tq), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, tq), jnp.float32)
        perm = [(i, (i - 1) % n_ring) for i in range(n_ring)]

        def step(s, carry):
            o, m, l, kc, vc = carry
            # kc currently holds the block originally owned by (my_idx + s)
            k_owner = (my_idx + s) % n_ring
            pv, m_blk, l_blk = _block_attn(
                q_blk, kc, vc,
                q_offset=my_idx * t_local,
                k_offset=k_owner * t_local,
                causal=causal, scale=scale_val)
            # online softmax merge
            m_new = jnp.maximum(m, m_blk)
            alpha = jnp.exp(m - m_new)        # rescale old accumulators
            beta = jnp.exp(m_blk - m_new)     # rescale new block
            l_new = l * alpha + l_blk * beta
            o_new = (o * jnp.swapaxes(alpha, 1, 2)[..., None]
                     + pv.astype(jnp.float32) * jnp.swapaxes(beta, 1, 2)[..., None])
            # rotate k/v to the next device (overlaps with next block's math)
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            return (o_new, m_new, l_new, kc, vc)

        o, m, l, _, _ = lax.fori_loop(
            0, n_ring, step, (o, m, l, k_blk.astype(jnp.float32),
                              v_blk.astype(jnp.float32)))
        denom = jnp.maximum(jnp.swapaxes(l, 1, 2)[..., None], 1e-30)
        return (o / denom).astype(q_blk.dtype)

    spec = P(None, axis_name, None, None)
    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return sharded(q, k, v)


def _win_steps(window: int, t_local: int, n_ring: int) -> int:
    """Ring hops a causal band ``(q-window, q]`` can touch: the diagonal
    block plus ``ceil((window-1)/t_local)`` previous neighbors, capped at
    the ring size."""
    return min(n_ring, -(-(window - 1) // t_local) + 1)


def _windowed_ring(q, k, v, mesh, *, axis_name, scale, window, n_ring,
                   t_local):
    """Causal sliding-window ring: only the ``n_steps`` hops whose k blocks
    can intersect any band run at all. The ring rotates so device i holds
    the block of owner ``(i - s) mod n`` at step s (previous neighbors
    first); owners "behind" the wrap are future blocks and contribute
    nothing (their merge weight is exp(-inf) = 0)."""
    # hops back to reach the band floor of a q block's FIRST position:
    # lowest visible k = i*t_local - window + 1 → owner i - ceil((w-1)/tl)
    n_steps = _win_steps(window, t_local, n_ring)
    # send i → i+1, so each device RECEIVES its predecessor's block
    perm = [(i, (i + 1) % n_ring) for i in range(n_ring)]

    def body(q_blk, k_blk, v_blk):
        my_idx = lax.axis_index(axis_name)
        b, tq, h, dd = q_blk.shape
        o = jnp.zeros((b, tq, h, dd), jnp.float32)
        m = jnp.full((b, h, tq), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, tq), jnp.float32)

        def step(s, carry):
            o, m, l, kc, vc = carry
            k_owner = (my_idx - s) % n_ring

            def compute(_):
                return _block_attn(
                    q_blk, kc, vc,
                    q_offset=my_idx * t_local,
                    k_offset=k_owner * t_local,
                    causal=True, scale=scale, window=window)

            def skip(_):
                return (jnp.zeros((b, tq, h, dd), jnp.float32),
                        jnp.full((b, h, tq), NEG_INF, jnp.float32),
                        jnp.zeros((b, h, tq), jnp.float32))

            # wrapped owners sit in the causal future of every local q
            pv, m_blk, l_blk = lax.cond(k_owner <= my_idx, compute, skip,
                                        None)
            m_new = jnp.maximum(m, m_blk)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(m_blk - m_new)
            l_new = l * alpha + l_blk * beta
            o_new = (o * jnp.swapaxes(alpha, 1, 2)[..., None]
                     + pv * jnp.swapaxes(beta, 1, 2)[..., None])
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            return (o_new, m_new, l_new, kc, vc)

        o, m, l, _, _ = lax.fori_loop(
            0, n_steps, step, (o, m, l, k_blk.astype(jnp.float32),
                               v_blk.astype(jnp.float32)))
        denom = jnp.maximum(jnp.swapaxes(l, 1, 2)[..., None], 1e-30)
        return (o / denom).astype(q_blk.dtype)

    spec = P(None, axis_name, None, None)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


class _WinRingConfig(_StaticConfig):
    """Hashable statics for the windowed flash-ring custom_vjp."""

    __slots__ = ("scale", "window", "n_ring", "t_local", "axis_name",
                 "interpret")

    def __init__(self, scale, window, n_ring, t_local, axis_name, interpret):
        self.scale = scale
        self.window = window
        self.n_ring = n_ring
        self.t_local = t_local
        self.axis_name = axis_name
        self.interpret = interpret

    @property
    def n_steps(self):
        return _win_steps(self.window, self.t_local, self.n_ring)

    def hop_kind(self, s: int) -> str:
        """STATIC per-hop classification (offset δ = s·t_local is
        device-independent in the reversed ring): "diag" (δ=0: the
        existing causal banded kernel applies), "full" (every (q, k) pair
        in-band: unmasked kernel, peak MXU), or "partial" (the band edge
        crosses this block: blockwise XLA math — at most two such hops,
        since the edge spans t_local positions)."""
        if s == 0:
            return "diag"
        # all pairs satisfy qi + δ - ki < window ⟺ (t_local-1) + δ < w
        return "full" if (s + 1) * self.t_local <= self.window else "partial"


def _win_partial_hop(cfg, q_blk, kc, vc, s):
    """One partial-band hop via blockwise XLA math → (o, lse) in the
    flash merge convention."""
    pv, m, l = _block_attn(q_blk.astype(jnp.float32), kc.astype(jnp.float32),
                           vc.astype(jnp.float32),
                           q_offset=s * cfg.t_local, k_offset=0,
                           causal=True, scale=cfg.scale, window=cfg.window)
    l_safe = jnp.maximum(l, 1e-30)
    o = pv / jnp.swapaxes(l_safe, 1, 2)[..., None]
    lse = m + jnp.log(l_safe)
    return o, lse


def _win_ring_fwd_impl(cfg, q_blk, k_blk, v_blk):
    """Forward windowed flash ring. The hop loop is UNROLLED (n_steps is
    small by construction), making each hop's q↔k offset a static
    s·t_local — which is what lets hops use the Pallas kernels: the diag
    hop runs the causal banded kernel, fully-in-band hops run the
    unmasked kernel, and only band-edge hops fall back to fused XLA
    blockwise math."""
    from deeplearning4j_tpu.pallas.flash_attention import (
        MASK_VALUE, flash_attention_fwd)

    axis = cfg.axis_name
    my_idx = lax.axis_index(axis)
    b, tq, h, d = q_blk.shape
    # reversed rotation: device i receives its predecessor's block
    perm = [(i, (i + 1) % cfg.n_ring) for i in range(cfg.n_ring)]

    def hop(s, kc, vc):
        kind = cfg.hop_kind(s)

        def compute(kv):
            kc, vc = kv
            if kind == "diag":
                return flash_attention_fwd(
                    q_blk, kc, vc, causal=True, window=cfg.window,
                    scale=cfg.scale, interpret=cfg.interpret)
            if kind == "full":
                return flash_attention_fwd(
                    q_blk, kc, vc, causal=False, scale=cfg.scale,
                    interpret=cfg.interpret)
            o, lse = _win_partial_hop(cfg, q_blk, kc, vc, s)
            return o.astype(q_blk.dtype), lse

        def skip(kv):
            return (jnp.zeros((b, tq, h, d), q_blk.dtype),
                    jnp.full((b, h, tq), MASK_VALUE, jnp.float32))

        # wrapped owners (my_idx < s) sit in the causal future: skip
        return lax.cond(my_idx >= s, compute, skip, (kc, vc))

    o = jnp.zeros((b, tq, h, d), jnp.float32)
    lse = jnp.full((b, h, tq), MASK_VALUE, jnp.float32)
    kc, vc = k_blk, v_blk
    for s in range(cfg.n_steps):
        o_blk, lse_blk = hop(s, kc, vc)
        o, lse = _lse_merge(o, lse, o_blk, lse_blk)
        if s + 1 < cfg.n_steps:
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
    return o.astype(q_blk.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _win_ring_flash(cfg, q_blk, k_blk, v_blk):
    out, _ = _win_ring_fwd_impl(cfg, q_blk, k_blk, v_blk)
    return out


def _win_ring_fwd_rule(cfg, q_blk, k_blk, v_blk):
    out, lse = _win_ring_fwd_impl(cfg, q_blk, k_blk, v_blk)
    return out, (q_blk, k_blk, v_blk, out, lse)


def _win_ring_bwd_rule(cfg, res, do):
    """Second windowed ring pass: dq accumulates locally; (dk, dv)
    accumulators travel with their k/v blocks through the same n_steps
    hops, then ONE ppermute of offset n_steps-1 carries them home (the
    full flash ring completes the circle instead; a windowed ring
    doesn't, so the trip home is explicit). Per-hop grads mirror the
    forward trichotomy: Pallas banded/unmasked kernels for diag/full
    hops, the offset-aware XLA scan backward for band-edge hops."""
    from deeplearning4j_tpu.pallas.flash_attention import (
        flash_backward, flash_backward_pallas)

    q_blk, k_blk, v_blk, out, lse = res
    axis = cfg.axis_name
    my_idx = lax.axis_index(axis)
    b, tq, h, d = q_blk.shape
    perm = [(i, (i + 1) % cfg.n_ring) for i in range(cfg.n_ring)]

    def hop_grads(s, kc, vc):
        kind = cfg.hop_kind(s)

        def compute(kv):
            kc, vc = kv
            if kind == "diag":
                return flash_backward_pallas(
                    q_blk, kc, vc, out, lse, do, causal=True,
                    window=cfg.window, scale=cfg.scale,
                    interpret=cfg.interpret)
            if kind == "full":
                return flash_backward_pallas(
                    q_blk, kc, vc, out, lse, do, causal=False,
                    scale=cfg.scale, interpret=cfg.interpret)
            dq, dk, dv = flash_backward(
                q_blk, kc, vc, out, lse, do, causal=True,
                window=cfg.window, q_offset=s * cfg.t_local, k_offset=0,
                scale=cfg.scale)
            return dq, dk, dv

        def skip(kv):
            return (jnp.zeros((b, tq, h, d), jnp.float32),
                    jnp.zeros((b, tq, h, d), jnp.float32),
                    jnp.zeros((b, tq, h, d), jnp.float32))

        return lax.cond(my_idx >= s, compute, skip, (kc, vc))

    dq = jnp.zeros((b, tq, h, d), jnp.float32)
    dkc = jnp.zeros((b, tq, h, d), jnp.float32)
    dvc = jnp.zeros((b, tq, h, d), jnp.float32)
    kc, vc = k_blk, v_blk
    for s in range(cfg.n_steps):
        dq_c, dk_c, dv_c = hop_grads(s, kc, vc)
        dq = dq + dq_c
        dkc = dkc + dk_c.astype(jnp.float32)
        dvc = dvc + dv_c.astype(jnp.float32)
        if s + 1 < cfg.n_steps:
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            dkc = lax.ppermute(dkc, axis, perm)
            dvc = lax.ppermute(dvc, axis, perm)
    # after n_steps-1 rotations device i's accumulators belong to owner
    # (i - (n_steps-1)) mod n — send them home in one hop
    if cfg.n_steps > 1:
        home = [(i, (i - (cfg.n_steps - 1)) % cfg.n_ring)
                for i in range(cfg.n_ring)]
        dkc = lax.ppermute(dkc, axis, home)
        dvc = lax.ppermute(dvc, axis, home)
    return (dq.astype(q_blk.dtype), dkc.astype(k_blk.dtype),
            dvc.astype(v_blk.dtype))


_win_ring_flash.defvjp(_win_ring_fwd_rule, _win_ring_bwd_rule)


def _windowed_ring_flash(q, k, v, mesh, *, axis_name, scale, window,
                         n_ring, t_local, interpret):
    cfg = _WinRingConfig(scale, window, n_ring, t_local, axis_name,
                         interpret)
    spec = P(None, axis_name, None, None)
    return shard_map(functools.partial(_win_ring_flash, cfg), mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)(q, k, v)


def ring_self_attention_sharded(mesh: Mesh):
    """Convenience: returns a jitted fn(q, k, v, causal) bound to ``mesh``."""

    @functools.partial(jax.jit, static_argnames=("causal",))
    def fn(q, k, v, causal=False):
        return ring_attention(q, k, v, mesh, causal=causal)

    return fn
