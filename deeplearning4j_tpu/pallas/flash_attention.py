"""Flash attention: blockwise online-softmax Pallas kernel for TPU.

Memory-optimal attention (Dao et al. flash attention recast for the TPU
memory hierarchy): the [t, t] score matrix never leaves VMEM — the kernel
streams K/V blocks through the MXU while carrying a running max and
normalizer per query row, so HBM traffic is O(t·d) instead of O(t²).
Greenfield relative to the reference (pre-transformer codebase — SURVEY §5
"no attention of any kind"); the native-kernel analogue is the role
libnd4j's hand-tuned ops played (deeplearning4j-core/pom.xml:154-158).

Three entry points:

- ``flash_attention_fwd(q, k, v, ...) -> (out, lse)`` — the raw kernel
  launch (no autodiff). ``lse`` (log-sum-exp per query row) is what makes
  blockwise composition possible: two attention outputs over disjoint key
  sets merge exactly via ``logaddexp`` — ring attention uses this.
- ``flash_attention(q, k, v, ...)`` — differentiable ``custom_vjp``
  wrapper. The backward pass is the standard flash recomputation: given
  the forward's ``lse`` and ``delta = Σ o·do``, each K/V block's gradient
  contribution is independent, so it runs as a ``lax.scan`` over key
  blocks with O(t·block) live memory and XLA fusing the blockwise math.
- ``flash_default_interpret()`` — True when the backend has no Mosaic
  compiler (CPU tests run the same kernel through the Pallas interpreter).

Layout is BTHD ([batch, time, heads, head_dim]) to match
``ops.attention.dot_product_attention``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -1e30
_LANES = 128  # running max/normalizer replicated across one lane tile


def flash_default_interpret() -> bool:
    """Interpret the kernel when no TPU backend is attached (CPU tests)."""
    return jax.devices()[0].platform != "tpu"


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, n_k, kv_len, window,
                n_band):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    if n_band is None:
        ki, last = j, n_k - 1
    else:
        # banded scan: j indexes the k blocks this q block's window can
        # touch; the index map fetched the SAME base+j block, and the
        # band condition below masks any non-intersecting tile
        ki = _band_base(qi, block_q, block_k, window, n_k, n_band) + j
        last = n_band - 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        # native-dtype matmul (bf16 feeds the MXU at full rate) with f32
        # accumulation via preferred_element_type
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        q_pos = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len  # kv padding
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_ref[...]                              # [block_q, LANES]
        m_cur = jnp.max(s, axis=1, keepdims=True)        # [block_q, 1]
        m_next = jnp.maximum(m_prev, m_cur)              # broadcast
        p = jnp.exp(s - m_next[:, :1])
        # zero fully-masked entries: when every score in the row is masked
        # m == MASK_VALUE and exp(s - m) would be 1, not 0
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_next)                  # [block_q, LANES]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv
        m_ref[...] = m_next

    _when_block_in_band(causal, qi, ki, block_q, block_k, window, _compute)

    @pl.when(j == last)
    def _finalize():
        l = l_ref[...]                         # [block_q, LANES] replicated
        safe_l = jnp.where(l == 0.0, 1.0, l)   # fully-masked query rows
        o_ref[0] = (acc_ref[...] / safe_l[:, :1]).astype(o_ref.dtype)
        # lse replicated across the lane dim (TPU block tiling needs a
        # 128-wide last axis; the wrapper slices lane 0)
        lse_ref[0] = m_ref[...] + jnp.log(safe_l)


def _when_block_in_band(causal, qi, ki, block_q, block_k, window, fn):
    """Run ``fn`` unless the whole tile is dead: above the causal
    diagonal or (sliding window) entirely below the band. The banded
    grids' end-clamps only shift scans over tiles these conditions
    mask, so no extra range check is needed."""
    cond = None
    if causal:
        cond = qi * block_q + block_q - 1 >= ki * block_k
    if window is not None:
        below = ki * block_k + block_k - 1 >= qi * block_q - window + 1
        cond = below if cond is None else cond & below
    if cond is None:
        fn()
    else:
        @pl.when(cond)
        def _():
            fn()


def _band_width(window, block_q, block_k, n_blocks):
    """How many k blocks a q block's window can intersect (capped)."""
    return min(n_blocks, -(-(window + block_q - 1) // block_k) + 1)


def _band_base(qi, block_q, block_k, window, n_blocks, n_band):
    """First k-block index of the ``n_band`` blocks scanned for q block
    ``qi``: the window's first visible block, clamped so the scanned
    range stays inside [0, n_blocks) (the clamp only shifts the range
    over blocks the band condition masks anyway)."""
    first = (qi * block_q - (window - 1)) // block_k
    return jnp.clip(first, 0, n_blocks - n_band)


def _round128(t: int) -> int:
    """Round up to the TPU lane-tile multiple used for block clamping."""
    return -(-t // 128) * 128


def _flat_heads(x):
    """[b, t, h, d] -> [b*h, t, d] (the kernels' batch-of-heads layout)."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _pad_time(x, block):
    """Zero-pad axis 1 (time) up to a multiple of ``block``."""
    pad = (-x.shape[1]) % block
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[1] = (0, pad)
        x = jnp.pad(x, widths)
    return x


def flash_attention_fwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Kernel launch. q: [b, tq, h, d]; k/v: [b, tkv, h, d].
    ``window`` (requires ``causal``) keeps k in (q-window, q] —
    sliding-window local attention on an O(t·window) BANDED grid: each
    q block's scan visits only the k blocks its window can touch
    (``_band_base``/``_band_width`` drive both the index maps and the
    in-kernel block ids), so grid steps and K/V DMA scale with the
    window, not t².

    Returns ``(out [b, tq, h, d], lse [b, h, tq])`` with no autodiff rule —
    use :func:`flash_attention` for training. ``causal`` assumes q and k
    index the same absolute positions (self-attention). Default blocks are
    the measured v5e sweet spot (t=8192: 2× the XLA-fused path); both are
    clamped to the (128-padded) sequence length for short inputs.
    """
    if interpret is None:
        interpret = flash_default_interpret()
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    b, tq, h, d = q.shape
    tkv = k.shape[1]
    if causal and tq != tkv:
        # the kernel's causal mask assumes q row i and k column i are the
        # SAME absolute position; with tq != tkv that silently mis-masks.
        # Cross-attention over different spans must use ring_attention /
        # flash_backward's explicit q_offset/k_offset instead.
        raise ValueError(
            f"flash_attention(causal=True) requires tq == tkv (got "
            f"tq={tq}, tkv={tkv}); self-attention positions must align")
    block_q = min(block_q, _round128(tq))
    block_k = min(block_k, _round128(tkv))
    scale_val = scale if scale is not None else float(1.0 / (d ** 0.5))

    qf = _pad_time(_flat_heads(q), block_q)
    kf = _pad_time(_flat_heads(k), block_k)
    vf = _pad_time(_flat_heads(v), block_k)
    tq_p, tkv_p = qf.shape[1], kf.shape[1]
    n_q, n_k = tq_p // block_q, tkv_p // block_k

    # windowed: scan only the k blocks intersecting each q block's band
    # (O(t*window) grid + DMA instead of O(t^2))
    n_band = None if window is None else _band_width(window, block_q,
                                                     block_k, n_k)
    if n_band is None:
        k_idx = lambda bh, qi, j: (bh, j, 0)
        grid_k = n_k
    else:
        def k_idx(bh, qi, j):
            return (bh, _band_base(qi, block_q, block_k, window,
                                   n_k, n_band) + j, 0)
        grid_k = n_band
    kernel = functools.partial(
        _fwd_kernel, scale=scale_val, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k, kv_len=tkv,
        window=window, n_band=n_band)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, grid_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, j: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), k_idx),
            pl.BlockSpec((1, block_k, d), k_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq_p, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            # bh/q blocks are independent; only the k scan carries state
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)

    out = out[:, :tq].reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    lse = lse[:, :tq, 0].reshape(b, h, tq)
    return out, lse


def flash_backward(q, k, v, out, lse, do, *, causal: bool = False,
                   scale: Optional[float] = None, block_k: int = 1024,
                   q_offset=0, k_offset=0, window: Optional[int] = None,
                   precise: bool = False):
    """Chunked flash backward (XLA scan). The production paths use the
    Pallas kernels (:func:`flash_backward_pallas`, used by both the
    custom_vjp and the ring backward); this scan version remains as the
    independently-derived reference implementation the kernel parity
    tests check against, and as the only path supporting arbitrary
    position offsets: ``q_offset``/``k_offset`` are the absolute
    positions of q[0] / k[0] (may be traced), ``lse``/``delta`` must
    come from the FULL merged attention.

    q/out/do: [b, tq, h, d]; k/v: [b, tkv, h, d]; lse: [b, h, tq].
    Returns (dq, dk, dv) in the input layouts (float32).

    ``precise=True`` runs every matmul with f32 OPERANDS. Parity tests
    use it so the oracle is genuinely higher-precision than the bf16
    kernels — with both sides casting operands to the input dtype, a
    shared reduced-precision bug class would cancel out and hide.
    """
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    b, tq, h, d = q.shape
    tkv = k.shape[1]
    block_k = min(block_k, _round128(tkv))
    scale_val = scale if scale is not None else float(1.0 / (d ** 0.5))
    # matmul operands stay in the INPUT dtype (bf16 under the mixed
    # policy) with f32 accumulation via preferred_element_type — casting
    # them to f32 would run every backward einsum at the f32 MXU rate.
    # Softmax math (p, ds, delta) stays f32. (precise=True overrides for
    # the oracle use-case above.)
    op_dtype = jnp.float32 if precise else q.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    q = q.astype(op_dtype)
    k = k.astype(op_dtype)
    v = v.astype(op_dtype)
    dof = do.astype(op_dtype)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                                 # [b, tq, h]
    delta = delta.transpose(0, 2, 1)                         # [b, h, tq]

    kp = _pad_time(k, block_k)
    vp = _pad_time(v, block_k)
    n_blocks = kp.shape[1] // block_k
    # [n_blocks, b, block_k, h, d]
    kb = kp.reshape(b, n_blocks, block_k, h, d).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(b, n_blocks, block_k, h, d).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(tq)

    def step(dq, blk):
        j, kj, vj = blk
        k_pos = k_offset + j * block_k + jnp.arange(block_k)
        s = mm("bqhd,bkhd->bhqk", q, kj) * scale_val
        valid = (k_pos < k_offset + tkv)[None, :]
        if causal:
            valid = valid & (q_pos[:, None] >= k_pos[None, :])
        if window is not None:
            valid = valid & (q_pos[:, None] - k_pos[None, :] < window)
        s = jnp.where(valid[None, None], s, MASK_VALUE)
        p = jnp.exp(s - lse[..., None])          # [b, h, tq, block_k] f32
        p = jnp.where(valid[None, None], p, 0.0)
        dv_j = mm("bhqk,bqhd->bkhd", p.astype(q.dtype), dof)
        dp = mm("bqhd,bkhd->bhqk", dof, vj)
        ds = p * (dp - delta[..., None]) * scale_val
        ds_c = ds.astype(q.dtype)
        dq = dq + mm("bhqk,bkhd->bqhd", ds_c, kj)
        dk_j = mm("bhqk,bqhd->bkhd", ds_c, q)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((b, tq, h, d), jnp.float32)
    dq, (dkb, dvb) = lax.scan(step, dq0,
                              (jnp.arange(n_blocks), kb, vb))
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(b, -1, h, d)[:, :tkv]
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(b, -1, h, d)[:, :tkv]
    return dq, dk, dv


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *,
              qi, ki, scale, causal, block_q, block_k, q_len, kv_len,
              window):
    """Shared backward tile math, kv-major ([block_k, block_q]) so the
    per-query lse/delta — ``[1, block_q]`` rows, queries on lanes —
    broadcast over sublanes with no transposes. Returns ``(p, ds)`` in
    f32; the score tile never leaves VMEM."""
    q = q_ref[0]            # [block_q, d]
    k = k_ref[0]            # [block_k, d]
    v = v_ref[0]
    do = do_ref[0]          # [block_q, d]
    lse = lse_ref[0]        # [1, block_q] f32 row (queries on lanes)
    delta = delta_ref[0]    # [1, block_q] f32
    s = lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    k_pos = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    valid = (q_pos < q_len) & (k_pos < kv_len)
    if causal:
        valid &= q_pos >= k_pos
    if window is not None:
        valid &= q_pos - k_pos < window
    s = jnp.where(valid, s, MASK_VALUE)
    # masked entries: exp(MASK - lse) == 0 for any finite lse (padded
    # query rows pad lse with 0), so no post-exp zeroing is needed
    p = jnp.exp(s - lse)                     # [block_k, block_q] f32
    dp = lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return p, ds


def _q_band_base(ki, block_q, block_k, n_blocks, n_band):
    """First q-block index scanned for key block ``ki``: causality puts
    the band's START at q == k (window-independent — only the WIDTH
    depends on the window, via _q_band_width); clamped so the range
    stays in [0, n_blocks)."""
    first = (ki * block_k) // block_q
    return jnp.clip(first, 0, n_blocks - n_band)


def _q_band_width(window, block_q, block_k, n_blocks):
    return min(n_blocks, -(-(block_k + window - 1) // block_q) + 1)


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                     block_q, block_k, n_q, q_len, kv_len, window,
                     n_band):
    """dk/dv for one key block, scanning query blocks (banded when
    windowed: only q blocks with k in their window)."""
    ki = pl.program_id(1)
    j = pl.program_id(2)
    if n_band is None:
        qi, last = j, n_q - 1
    else:
        qi = _q_band_base(ki, block_q, block_k, n_q, n_band) + j
        last = n_band - 1

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        p, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          qi=qi, ki=ki, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_len=q_len, kv_len=kv_len, window=window)
        q, do = q_ref[0], do_ref[0]
        dv_acc[...] += lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _when_block_in_band(causal, qi, ki, block_q, block_k, window,
                        _compute)

    @pl.when(j == last)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k,
                   n_k, q_len, kv_len, window, n_band):
    """dq for one query block, scanning key blocks (kv-major tiles;
    banded to the window when set)."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    if n_band is None:
        ki, last = j, n_k - 1
    else:
        ki = _band_base(qi, block_q, block_k, window, n_k, n_band) + j
        last = n_band - 1

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute():
        _, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          qi=qi, ki=ki, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_len=q_len, kv_len=kv_len, window=window)
        k = k_ref[0]
        # contract over the key dim (sublanes): [bk, bq]^T x [bk, d]
        dq_acc[...] += lax.dot_general(
            ds.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _when_block_in_band(causal, qi, ki, block_q, block_k, window,
                        _compute)

    @pl.when(j == last)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def flash_backward_pallas(q, k, v, out, lse, do, *, causal: bool = False,
                          scale: Optional[float] = None, block_q: int = 512,
                          block_k: int = 512,
                          interpret: Optional[bool] = None,
                          window: Optional[int] = None):
    """Pallas flash backward: the score/probability tiles stay in VMEM
    (two kernels: dk/dv over key blocks, dq over query blocks), unlike
    :func:`flash_backward` whose XLA scan round-trips O(t·block) f32
    temps through HBM. Aligned spans only (block-relative positions ==
    absolute): used by BOTH the custom_vjp and the ring backward, whose
    full/diag/skip block trichotomy never needs offsets.

    Returns (dq, dk, dv) as float32 in the input layouts.
    """
    if interpret is None:
        interpret = flash_default_interpret()
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    b, tq, h, d = q.shape
    tkv = k.shape[1]
    block_q = min(block_q, _round128(tq))
    block_k = min(block_k, _round128(tkv))
    scale_val = scale if scale is not None else float(1.0 / (d ** 0.5))

    qf = _pad_time(_flat_heads(q), block_q)
    dof = _pad_time(_flat_heads(do.astype(q.dtype)), block_q)
    kf = _pad_time(_flat_heads(k), block_k)
    vf = _pad_time(_flat_heads(v), block_k)
    tq_p, tkv_p = qf.shape[1], kf.shape[1]
    n_q, n_k = tq_p // block_q, tkv_p // block_k

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                       # [b, tq, h]
    # per-query rows as [b*h, 1, tq]: a (1, 1, block_q) block keeps the
    # queries on lanes (the kv-major tiles broadcast it over sublanes) and
    # its sublane extent equals the array's, which Mosaic's block rule
    # needs — a (1, block_q) block over [b*h, tq] has a sublane extent of
    # 1 that neither divides by 8 nor spans the array, and is refused
    delta = delta.transpose(0, 2, 1).reshape(b * h, 1, tq)
    lse_f = lse.reshape(b * h, 1, tq)
    pad_q = tq_p - tq
    if pad_q:
        # padded q rows: lse=0 pairs with the MASK_VALUE scores so
        # exp(MASK - 0) == 0 — they contribute nothing
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))
        lse_f = jnp.pad(lse_f, ((0, 0), (0, 0), (0, pad_q)))

    common = dict(scale=scale_val, causal=causal,
                  block_q=block_q, block_k=block_k,
                  q_len=tq, kv_len=tkv, window=window)

    def specs(q_idx, k_idx):
        """Input specs for a (bh, i, j) grid; q/do/lse/delta blocks follow
        ``q_idx(i, j)``, k/v blocks follow ``k_idx(i, j)``."""
        return [
            pl.BlockSpec((1, block_q, d),
                         lambda bh, i, j: (bh, q_idx(i, j), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j: (bh, k_idx(i, j), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j: (bh, k_idx(i, j), 0)),
            pl.BlockSpec((1, block_q, d),
                         lambda bh, i, j: (bh, q_idx(i, j), 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bh, i, j: (bh, 0, q_idx(i, j))),
            pl.BlockSpec((1, 1, block_q),
                         lambda bh, i, j: (bh, 0, q_idx(i, j))),
        ]

    # banded grids when windowed: dkdv scans only q blocks whose window
    # reaches its k block; dq scans only k blocks in its q block's band
    if window is None:
        nb_q = nb_k = None
        dkdv_q = lambda i, j: j
        dq_k = lambda i, j: j
        grid_dkdv, grid_dq = n_q, n_k
    else:
        nb_q = _q_band_width(window, block_q, block_k, n_q)
        nb_k = _band_width(window, block_q, block_k, n_k)
        dkdv_q = lambda i, j: _q_band_base(i, block_q, block_k,
                                           n_q, nb_q) + j
        dq_k = lambda i, j: _band_base(i, block_q, block_k, window,
                                       n_k, nb_k) + j
        grid_dkdv, grid_dq = nb_q, nb_k

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, n_q=n_q, n_band=nb_q, **common),
        grid=(b * h, n_k, grid_dkdv),
        in_specs=specs(q_idx=dkdv_q, k_idx=lambda i, j: i),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tkv_p, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, tkv_p, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lse_f, delta)

    (dq,) = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=n_k, n_band=nb_k, **common),
        grid=(b * h, n_q, grid_dq),
        in_specs=specs(q_idx=lambda i, j: i, k_idx=dq_k),
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq_p, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lse_f, delta)

    def _unflat(x, t):
        return x[:, :t].reshape(b, h, t, d).transpose(0, 2, 1, 3)

    return _unflat(dq, tq), _unflat(dk, tkv), _unflat(dv, tkv)


class _FlashConfig:
    """Hashable static config for the custom_vjp nondiff argument."""

    __slots__ = ("causal", "scale", "block_q", "block_k", "interpret",
                 "window")

    def __init__(self, causal, scale, block_q, block_k, interpret,
                 window=None):
        self.causal = causal
        self.scale = scale
        self.block_q = block_q
        self.block_k = block_k
        self.interpret = interpret
        self.window = window

    def _key(self):
        return (self.causal, self.scale, self.block_q, self.block_k,
                self.interpret, self.window)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return (isinstance(other, _FlashConfig)
                and self._key() == other._key())


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _FlashConfig, q, k, v):
    out, _ = flash_attention_fwd(
        q, k, v, causal=cfg.causal, scale=cfg.scale, block_q=cfg.block_q,
        block_k=cfg.block_k, interpret=cfg.interpret, window=cfg.window)
    return out


def _flash_fwd_rule(cfg, q, k, v):
    out, lse = flash_attention_fwd(
        q, k, v, causal=cfg.causal, scale=cfg.scale, block_q=cfg.block_q,
        block_k=cfg.block_k, interpret=cfg.interpret, window=cfg.window)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(cfg, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = flash_backward_pallas(
        q, k, v, out, lse, do, causal=cfg.causal, scale=cfg.scale,
        block_q=cfg.block_q, block_k=cfg.block_k, interpret=cfg.interpret,
        window=cfg.window)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Differentiable flash attention. q: [b, tq, h, d] → [b, tq, h, d].

    Drop-in for ``ops.attention.dot_product_attention(q, k, v, causal=...)``
    when there is no padding mask / additive bias (callers with those fall
    back to the reference op).
    """
    if interpret is None:
        interpret = flash_default_interpret()
    cfg = _FlashConfig(causal, scale, block_q, block_k, interpret, window)
    return _flash(cfg, q, k, v)
