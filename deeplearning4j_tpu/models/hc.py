"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): the
residual of a token is ``n`` streams, ``X`` [n, D], and every sub-layer ``F``
(a layer's mixer, then its feed-forward) reads a weighted sum of them and
writes back through a doubly-stochastic ``n x n`` map, as
``TransformerLM(hc=)`` runs it.

Per token and sub-layer, with the sub-layer's own ``phi`` [n D, n + n + n n]
(columns: pre, post, res), ``alpha`` [3] and ``b`` [n + n + n n]:

    x~      = rmsnorm(vec(X))                  over n D, no gain
    H_pre   = sigmoid(alpha_pre  (x~ phi_pre)  + b_pre)             [n]
    H_post  = 2 sigmoid(alpha_post (x~ phi_post) + b_post)          [n]
    M_0     = exp(alpha_res mat(x~ phi_res) + b_res)                [n, n]
    M_k     = cols(rows(M_{k-1}))              rows(M) = M / (M 1 + eps)
    H_res   = M_iters                          cols(M) = M / (1^T M + eps)
    u       = H_pre X                          the sub-layer's input [D]
    X'      = H_res X + H_post^T F(norm(u))    stream i gains H_post[i] F(.)

The streams start as ``n`` copies of the token's embedding (``expand``) and
the final norm reads their sum (``collapse``).

The maps are float32 whatever the streams are stored in: the projection at
``HIGHEST`` precision (16,384 x 24 at GLM-5.3's widths: nothing beside a
layer's weights), and the Sinkhorn sweeps, every one of them computed, with
the **tokens on the minor axis** (``M`` is ``[n, n, N]``): a sweep is then a
dozen element-wise ops over whole vector registers that XLA fuses into one
loop, where ``[N, n, n]`` would pad every 4 x 4 matrix to an 8 x 128 tile.
The sweeps are unrolled at trace time (a ``while`` of forty tiny ops would
be forty launches a sub-layer on a TPU). The mix is written as the ``n`` (or
``n n``) scaled adds it is, over ``[N, D]`` streams: element-wise and bound
by the streams' bytes, no matmul of contraction 4.

Scopes ``hc.map`` (norm, projection, sigmoids, Sinkhorn) and ``hc.mix``
(``H_pre X``; ``H_res X + H_post^T y``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.scopes import scope

__all__ = ["init_hc", "expand", "collapse", "maps", "sinkhorn", "read",
           "write"]


def init_hc(key, d_model: int, dims: Dict[str, Any], dtype) -> Dict[str, Any]:
    """One sub-layer's parameters: ``phi`` [n D, 2 n + n n] normal x 0.02,
    ``alpha`` [3] 0.01 and ``b`` = [0.., 0.., 4 I]: maps that start near the
    plain residual (``H_pre`` 1/2 each, ``H_post`` 1, ``H_res`` near I), as
    the paper's. A benchmark draws its own."""
    n = int(dims["streams"])
    b_res = 4.0 * jnp.eye(n, dtype=dtype).reshape(-1)
    return {"phi": jax.random.normal(key, (n * d_model, 2 * n + n * n),
                                     dtype) * 0.02,
            "alpha": jnp.full((3,), 0.01, dtype),
            "b": jnp.concatenate([jnp.zeros((2 * n,), dtype), b_res])}


def expand(h, n: int):
    """The entry: ``h`` [b, t, D] -> ``X`` [b, t, n, D], the embedding in
    each stream."""
    with scope("hc.mix"):
        return jnp.broadcast_to(h[:, :, None, :],
                                h.shape[:2] + (n, h.shape[-1]))


def collapse(x):
    """The exit: ``X`` [..., n, D] -> [..., D], the sum of the streams (in
    float32, rounded once)."""
    with scope("hc.mix"):
        return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


def sinkhorn(m, iters: int, eps: float):
    """``iters`` sweeps, rows then columns, over ``m`` [n, n, N] (positive;
    row i, column j, token on the minor axis). Every sweep is computed:
    after them the columns sum to 1 to within ``eps`` and the rows as
    nearly as ``iters`` sweeps bring them."""
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def maps(x, p, *, dims: Dict[str, Any], norm_eps: float
         ) -> Tuple[Any, Any, Any]:
    """``X`` [b, t, n, D] -> ``(H_pre [n, b, t], H_post [n, b, t], H_res
    [n, n, b, t])`` float32, the sub-layer's three maps of every token."""
    b, t, n, d = x.shape
    f32 = jnp.float32
    with scope("hc.map"):
        flat = x.astype(f32).reshape(b * t, n * d)
        flat = flat * lax.rsqrt(
            jnp.mean(flat * flat, axis=-1, keepdims=True) + norm_eps)
        z = jnp.dot(flat, p["phi"].astype(f32), precision=lax.Precision.HIGHEST)
        alpha, bias = p["alpha"].astype(f32), p["b"].astype(f32)
        scale = jnp.concatenate([jnp.full((n,), alpha[0]),
                                 jnp.full((n,), alpha[1]),
                                 jnp.full((n * n,), alpha[2])])
        z = (z * scale + bias).T                            # [2n + nn, N]
        pre = jax.nn.sigmoid(z[:n])
        post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
        res = sinkhorn(jnp.exp(z[2 * n:]).reshape(n, n, b * t),
                       dims["sinkhorn_iters"], float(dims["eps"]))
        return (pre.reshape(n, b, t), post.reshape(n, b, t),
                res.reshape(n, n, b, t))


def read(x, pre):
    """``u = H_pre X`` [b, t, D] in ``X``'s dtype: the sub-layer's input."""
    with scope("hc.mix"):
        xs = x.astype(jnp.float32)
        u = sum(pre[i][..., None] * xs[:, :, i] for i in range(x.shape[2]))
        return u.astype(x.dtype)


def write(x, y, post, res):
    """``X' = H_res X + H_post^T y`` [b, t, n, D] in ``X``'s dtype, ``y``
    [b, t, D] the sub-layer's output."""
    n = x.shape[2]
    with scope("hc.mix"):
        xs, ys = x.astype(jnp.float32), y.astype(jnp.float32)
        out = [sum(res[i, j][..., None] * xs[:, :, j] for j in range(n))
               + post[i][..., None] * ys for i in range(n)]
        return jnp.stack(out, axis=2).astype(x.dtype)
