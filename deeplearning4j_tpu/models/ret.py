"""Power retention (power attention, arXiv:2507.04239, with the gate of
Manifest AI's ``retention`` package: the mixer of ``brumby``), degree 2, as
``TransformerLM._block`` runs it.

Per key/value head ``h`` of ``Hkv``, serving the query heads ``h H/Hkv .. (h
+ 1) H/Hkv - 1`` (``x`` is the block's normed input, ``d`` the head size,
``p = 2``):

    q, k, v  = x W_q, x W_k, x W_v              no bias
    q, k     = rope(rmsnorm_head(q)), rope(rmsnorm_head(k))
    log g_t  = logsigmoid(x_t W_g + b_g)        one a key/value head, float32
    w_ij     = (q_i . k_j / sqrt(d))^p exp(sum_{l=j+1..i} log g_l)   j <= i
    o_i      = sum_j w_ij v_j / (sum_j w_ij + eps)
    y        = concat_heads(o) W_o

``p`` even makes every weight non-negative, so the normaliser is a plain
sum. With ``phi: R^d -> R^D``, ``phi(a) . phi(b) = (a . b)^2``, the same
thing is a recurrence on a state that has no time axis:

    S_t = g_t S_{t-1} + phi(k_t / d^(1/4)) v_t^T        [D, dv] float32
    Z_t = g_t Z_{t-1} + (k_t k_t^T) / sqrt(d)           [d, d]  float32
    o_t = S_t^T phi(q_t / d^(1/4)) / (q_t^T Z_t q_t / sqrt(d) + eps)

(``q^T Z q`` is ``z . phi(q)`` for ``z = sum phi(k)``: the normaliser is
kept as the whole symmetric matrix, 64 KiB a head beside 4.5 MB of ``S``, so
that neither form expands anything for it.)

**The layout stored.** ``phi(a)`` holds the products ``a_i a_j`` of the
block upper triangle in tiles of ``TILE`` = 8 along ``j``: every ``(i, j)``
with ``j >= 8 floor(i / 8)``, weight 1 inside a diagonal 8 x 8 block (where
both ``(i, j)`` and ``(j, i)`` are kept) and ``sqrt 2`` above it: ``D`` =
64 x 16 x 17 / 2 = 8,704 at ``d`` = 128 (the symmetric power's 8,256 plus
the lower halves of the 16 diagonal blocks, 5.4 %; the full tensor product
is 16,384). Rows run by block row ``I``, then by half ``p`` of its eight
``i``, then by block column ``J >= I``, then the half's four ``i``, then the
tile's eight ``j`` (``phi``): eight rows that share ``i`` are one sublane
tile of ``S`` whose ``phi`` is a scalar times eight consecutive entries of
``a``, which is what the decode kernel builds them from
(``pallas/retention_step.py``).

Two forms of the same recurrence, both taking the positions for RoPE (the
delta-rule mixers carry no position; this one does):

- ``ret_scan`` (a prompt, training): chunks of ``CHUNK`` positions under a
  ``lax.scan``. Inside a chunk the masked ``(q . k)^2`` times the gates'
  products (``exp(G_i - G_j)`` of the cumulated log-gates, never a
  quotient); between chunks the state: ``phi(q) S`` read and ``phi(k)^T v``
  folded in, once a chunk, so one chunk's ``[C, D]`` expansion is the only
  one alive, and that a block row at a time (``phi_pieces``): laid out
  whole, the queries' ``[C, D]`` is 178 MB in bf16 and a token and layer
  took 5.65 us on the chip, 10 % of the MXU's peak; a run of rows made
  where its product reads it, 1.69 us (TPU v5e, PR 47,
  ``scripts/retention_step_bench.py``). The matrix products take the
  compute dtype's operands and accumulate in float32 (float32 operands:
  at ``HIGHEST``); the state is float32.
- ``ret_step`` (decode): one position a row on the carried ``(S, Z)``, as
  XLA ops over every row; with ``kernel`` a serving decode step runs
  ``pallas/retention_step.py`` instead: the same arithmetic over the rows
  that owe a token only, their state moved once, in place.

A row that holds no token (``live`` false: a prompt's pad tail, a slot that
owes nothing) takes ``log g = 0`` and ``k = 0`` and so leaves the state as
it was (``kda.mask_dead``'s rule for this recurrence).

A prompt of more than ``2 SEQ_BLOCK`` positions runs ``SEQ_BLOCK`` at a
time, each block from the state the one before left (``models/gdn.py``'s
cut: the projections, norms and RoPE in float32 are one block's).

Scopes ``ret.proj``, ``ret.scan`` and ``ret.step`` name the parts in a
device trace.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.pallas.retention_step import retention_step
from deeplearning4j_tpu.scopes import scope

__all__ = ["CHUNK", "SEQ_BLOCK", "TILE", "EPS", "init_ret", "ret_mixer",
           "ret_scan", "ret_step", "phi", "phi_pieces", "state_rows",
           "gate_bias"]

CHUNK = 256
SEQ_BLOCK = 4096        # as ``gdn.SEQ_BLOCK``: the serving ladders' long rungs
TILE = 8                # the layout's tile along j (the module's docstring)
EPS = 1e-6              # the normaliser's
_HI = lax.Precision.HIGHEST
_SQRT2 = math.sqrt(2.0)


def state_rows(head_dim: int) -> int:
    """``D``: the rows of ``S`` a head, for the layout stored."""
    blocks = head_dim // TILE
    return TILE * TILE * blocks * (blocks + 1) // 2


def phi_pieces(a):
    """``phi(a)`` by block row: the ``d / 8`` runs of rows that share a block
    row ``I``, ``[..., 64 (d / 8 - I)]`` each, in the layout's order."""
    d = a.shape[-1]
    if d % TILE:
        raise ValueError(f"head_dim={d} must be a multiple of {TILE}")
    blocks, lead = d // TILE, a.shape[:-1]
    pieces = []
    for i in range(blocks):
        n = blocks - i
        rows = a[..., TILE * i:TILE * (i + 1)].reshape(lead + (2, 1, 4, 1))
        weight = jnp.asarray([1.0] + [_SQRT2] * (n - 1), a.dtype)
        cols = a[..., TILE * i:].reshape(lead + (1, n, 1, TILE)) \
            * weight[:, None, None]
        pieces.append((rows * cols).reshape(lead + (TILE * TILE * n,)))
    return pieces


def phi(a):
    """``a`` [..., d] -> [..., D] in the layout stored: ``phi(a) . phi(b) =
    (a . b)^2``."""
    return jnp.concatenate(phi_pieces(a), axis=-1)


def gate_bias(kv_heads: int, dtype):
    """``b_g``, seeded so that the heads' memories span a few to a thousand
    tokens: 2.5 .. 7 over the key/value heads. With a Glorot ``W_g`` the
    gate's input has a standard deviation of 1.4, so ``log g =
    logsigmoid(x W_g + b_g)`` lies about -0.3 .. -0.02 a position on the
    first head and -0.004 .. -0.0002 on the last; with ``b_g = 0`` the
    median is -0.69, every state holds two tokens and the mixer is a local
    average (PR 39's finding for the delta rule's gate)."""
    return jnp.linspace(2.5, 7.0, kv_heads).astype(dtype)


def init_ret(key, d_model: int, heads: int, kv_heads: int, head_dim: int,
             dtype) -> Dict[str, Any]:
    """Glorot-normal ``wq`` [D, H d], ``wk``, ``wv`` [D, Hkv d], ``wo``
    [H d, D], ``wg`` [D, Hkv]; ``bg`` [Hkv] (``gate_bias``); the per-head
    norms' gains ``q_norm.g``, ``k_norm.g`` [d] one."""
    ks = jax.random.split(key, 5)

    def glorot(k, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dtype)
        return jax.random.normal(k, (fan_in, fan_out), dtype) * scale

    return {"wq": glorot(ks[0], d_model, heads * head_dim),
            "wk": glorot(ks[1], d_model, kv_heads * head_dim),
            "wv": glorot(ks[2], d_model, kv_heads * head_dim),
            "wo": glorot(ks[3], heads * head_dim, d_model),
            "wg": glorot(ks[4], d_model, kv_heads),
            "bg": gate_bias(kv_heads, dtype),
            "q_norm": {"g": jnp.ones((head_dim,), dtype)},
            "k_norm": {"g": jnp.ones((head_dim,), dtype)}}


def _zero_state(b: int, kv_heads: int, d: int):
    return (jnp.zeros((b, kv_heads, state_rows(d), d), jnp.float32),
            jnp.zeros((b, kv_heads, d, d), jnp.float32))


def ret_step(q, k, v, lg, state):
    """The recurrence for one position a row: ``q`` [b, H, d], ``k``, ``v``
    [b, Hkv, d] (q and k already times ``d^-1/4``), ``lg`` [b, Hkv] (the
    log-gate), float32; ``state`` = ``(S [b, Hkv, D, d], Z [b, Hkv, d,
    d])``. Returns ``(o [b, H, d], (S, Z))``."""
    s, z = state
    b, h, d = q.shape
    hkv = k.shape[1]
    a = jnp.exp(lg)
    s = a[..., None, None] * s + phi(k)[..., None] * v[:, :, None, :]
    z = a[..., None, None] * z + k[..., :, None] * k[..., None, :]
    qg = q.reshape(b, hkv, h // hkv, d)
    num = jnp.einsum("bhrd,bhdv->bhrv", phi(qg), s, precision=_HI)
    den = jnp.einsum("bhri,bhij,bhrj->bhr", qg, z, qg, precision=_HI)
    return (num / (den[..., None] + EPS)).reshape(b, h, d), (s, z)


def ret_scan(q, k, v, lg, state, cdt=jnp.float32):
    """The chunked recurrence. ``q`` [b, t, H, d], ``k``, ``v`` [b, t, Hkv,
    d] (q and k already times ``d^-1/4``), ``lg`` [b, t, Hkv] (log-gate, <=
    0), float32; ``state`` = ``(S, Z)``; ``cdt`` the dtype the matrix
    products' operands take. Returns ``(o [b, t, H, d], (S, Z))``."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    c = min(CHUNK, t)
    pad = -t % c
    if pad:     # k = 0 and log g = 0: the tail moves no state
        q, k, v, lg = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (q, k, v, lg))
    n = (t + pad) // c

    def chunks(a):      # [b, n c, heads, ...] -> [n, b, heads, c, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 3, 2)

    keep = jnp.tril(jnp.ones((c, c), bool))
    exact = jnp.dtype(cdt) == jnp.float32

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(cdt), y.astype(cdt),
                          precision=_HI if exact else None,
                          preferred_element_type=jnp.float32)

    def step(carry, xs):
        s, z = carry
        qc, kc, vc, gc = xs         # [b, Hkv rep | Hkv, c, d] ... [b, Hkv, c]
        qc = qc.reshape(b, hkv, rep, c, d)
        cum = jnp.cumsum(gc, axis=2)                        # G_t  [b, Hkv, c]
        rel = jnp.exp(jnp.where(
            keep, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        w = mm("bhrtd,bhsd->bhrts", qc, kc) ** 2 * rel[:, :, None]
        into = jnp.exp(cum)[:, :, None, :, None]            # since the chunk
        # the products with the state a block row at a time: a run of phi is
        # made where its product reads it and the whole [c, D] never is
        read, lo = 0.0, 0
        for run in phi_pieces(qc):
            hi = lo + run.shape[-1]
            read = read + mm("bhrtD,bhDv->bhrtv", run, s[:, :, lo:hi])
            lo = hi
        num = mm("bhrts,bhsv->bhrtv", w, vc) + into * read
        den = jnp.sum(w, axis=-1, keepdims=True) + into * jnp.einsum(
            "bhrti,bhij,bhrtj->bhrt", qc, z, qc, precision=_HI)[..., None]
        last = cum[:, :, -1:]
        left = jnp.exp(last - cum)[..., None]               # to the chunk's end
        end = jnp.exp(last)[..., None]
        s = end * s + jnp.concatenate(
            [mm("bhsD,bhsv->bhDv", run, vc * left)
             for run in phi_pieces(kc)], axis=2)
        z = end * z + jnp.einsum("bhsi,bhsj->bhij", kc * left, kc,
                                 precision=_HI)
        return (s, z), (num / (den + EPS)).reshape(b, h, c, d)

    state, o = lax.scan(step, state,
                        tuple(chunks(a) for a in (q, k, v, lg)))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)           # [b, n, c, H, d]
    return o.reshape(b, n * c, h, d)[:, :t], state


def ret_mixer(x, p: Dict[str, Any], *, heads: int, kv_heads: int,
              rope: Callable, rmsnorm: Callable, positions,
              cast: Callable = lambda w: w, live=None, state=None,
              kernel: bool = False) -> Tuple[Any, Any, Any]:
    """The mixer on ``x`` [b, t, D] with the block's ``ret`` parameters
    ``p`` (``init_ret``). ``rope(a, positions)`` turns ``a`` [b, t, heads,
    d] at ``positions`` ([t] or [b, t]), ``rmsnorm(a, g)`` is the model's
    norm over a head. ``live`` [b, t] (bool, default all) marks the rows
    that hold a token; within a row they are a prefix. ``state`` = ``(S [b,
    Hkv, D, d], Z [b, Hkv, d, d])`` float32 is what the positions before
    ``x`` left (default: a request's start, zeros); with a state and ``t ==
    1`` the recurrence runs as ``ret_step``, or with ``kernel`` as the
    Pallas step over the live rows.

    Returns ``(y [b, t, D] in x.dtype, S, Z)``: the state as of each row's
    last live position."""
    b, t, _ = x.shape
    d = p["q_norm"]["g"].shape[0]
    f32 = jnp.float32
    if t > 2 * SEQ_BLOCK and t % SEQ_BLOCK == 0:
        # block after block, the state handed on (a block with no live row
        # hands on what it was given)
        if state is None:
            state = _zero_state(b, kv_heads, d)
        if live is None:
            live = jnp.ones((b, t), bool)
        positions = jnp.broadcast_to(positions, (b, t))

        def blocks(a):      # [b, n B, ...] -> [n, b, B, ...]
            return jnp.moveaxis(
                a.reshape((b, -1, SEQ_BLOCK) + a.shape[2:]), 1, 0)

        def one(carry, rows):
            y, s, z = ret_mixer(
                rows[0], p, heads=heads, kv_heads=kv_heads, rope=rope,
                rmsnorm=rmsnorm, positions=rows[2], cast=cast, live=rows[1],
                state=carry)
            return (s, z), y

        (s, z), y = lax.scan(one, state,
                             (blocks(x), blocks(live), blocks(positions)))
        return jnp.moveaxis(y, 0, 1).reshape(x.shape), s, z
    with scope("ret.proj"):
        q = (x @ cast(p["wq"])).reshape(b, t, heads, d)
        k = (x @ cast(p["wk"])).reshape(b, t, kv_heads, d)
        v = (x @ cast(p["wv"])).reshape(b, t, kv_heads, d).astype(f32)
        q = rope(rmsnorm(q, p["q_norm"]["g"]), positions)
        k = rope(rmsnorm(k, p["k_norm"]["g"]), positions)
        lg = jax.nn.log_sigmoid((x @ cast(p["wg"])).astype(f32)
                                + p["bg"].astype(f32))      # [b, t, Hkv]
        q = q.astype(f32) * d ** -0.25
        k = k.astype(f32) * d ** -0.25
        if live is not None:
            lg = jnp.where(live[:, :, None], lg, 0.0)
            k = jnp.where(live[:, :, None, None], k, 0.0)
        start = _zero_state(b, kv_heads, d) if state is None else state
    if state is not None and t == 1:
        with scope("ret.step"):
            if kernel:
                o, s, z = retention_step(
                    q[:, 0], k[:, 0], v[:, 0], lg[:, 0], *start,
                    None if live is None else live[:, 0], eps=EPS)
            else:
                o, (s, z) = ret_step(q[:, 0], k[:, 0], v[:, 0], lg[:, 0],
                                     start)
                if live is not None:
                    o = jnp.where(live[:, :, None], o, 0.0)
            o = o[:, None]
    else:
        with scope("ret.scan"):
            o, (s, z) = ret_scan(q, k, v, lg, start, x.dtype)
    with scope("ret.proj"):
        y = o.astype(x.dtype).reshape(b, t, -1) @ cast(p["wo"])
    return y, s, z
