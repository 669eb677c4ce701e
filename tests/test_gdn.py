"""Gated DeltaNet (``models/gdn.py``) on ``models/kda.py``'s two forms: the
chunked scan, the one-position step and the plain recurrence
(``benchmarks/lib/reference_qwen3_next.py``: one position after another)
agree; the scalar decay's products inside a chunk are matmuls; and the
per-channel form Ling stands on computes what it computed before.

float32 at ``highest`` on both sides: they differ in the order of sums only.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import reference_qwen3_next as ref  # noqa: E402
from deeplearning4j_tpu.models import gdn, kda  # noqa: E402

D, HK, DK, TAPS = 48, 2, 16, 4
TOL = 1e-5
EPS = 1e-6


def _dims(ratio=2):
    return {"key_heads": HK, "value_heads": HK * ratio, "head_dim": DK,
            "conv": TAPS}


def _cfg(ratio=2):
    return {"linear_num_key_heads": HK, "linear_num_value_heads": HK * ratio,
            "linear_key_head_dim": DK, "rms_norm_eps": EPS}


def _params(ratio=2, seed=0):
    p = gdn.init_gdn(jax.random.PRNGKey(seed), D, _dims(ratio), jnp.float32)
    k = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    hv = HK * ratio
    # A_log = log U(1, 16) and a drawn dt_bias: a gate that neither forgets
    # at once nor never; a drawn gain: a norm that forgot it would show
    p["a_log"] = jnp.log(jax.random.uniform(k[0], (hv,), minval=1.0,
                                            maxval=16.0))
    p["dt_bias"] = -2 + jax.random.normal(k[1], (hv,))
    p["o_norm"]["g"] = 1 + 0.1 * jax.random.normal(k[2], (DK,))
    return p


def _x(t, seed=0, b=1):
    return jax.random.normal(jax.random.PRNGKey(100 + seed), (b, t, D))


def _mix(x, p, ratio=2, **kw):
    return gdn.gdn_mixer(x, p, dims=_dims(ratio), eps=EPS, **kw)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ---- (a) the three forms agree ----------------------------------------------
@pytest.mark.parametrize("t", [1, 7, 64, 65, 128, 200])
def test_chunked_scan_is_the_recurrence(t):
    """Lengths below, at, above and between multiples of a chunk's 64
    positions: every position's output and the final state."""
    p = _params()
    x = _x(t, seed=t)
    y, s, tail = _mix(x, p)
    y_ref, s_ref = ref.gdn_mixer(x[0], p, _cfg())
    np.testing.assert_allclose(y[0], y_ref, atol=TOL)
    np.testing.assert_allclose(s[0], s_ref, atol=TOL)
    assert s.shape == (1, 2 * HK, DK, DK)
    assert tail.shape == (1, TAPS - 1, 4 * HK * DK)


@pytest.mark.parametrize("ratio", [1, 2, 4])
def test_value_heads_share_a_key_head(ratio):
    """Key head j serves value heads j r .. (j + 1) r - 1."""
    p = _params(ratio)
    x = _x(70, seed=ratio)
    y, s, _ = _mix(x, p, ratio)
    y_ref, s_ref = ref.gdn_mixer(x[0], p, _cfg(ratio))
    np.testing.assert_allclose(y[0], y_ref, atol=TOL)
    np.testing.assert_allclose(s[0], s_ref, atol=TOL)


@pytest.mark.parametrize("n,t", [(5, 16), (37, 64), (64, 128), (2, 16),
                                 (100, 128)])
def test_pad_rows_move_no_state(n, t):
    """A prompt of n tokens in a row of t: the live rows' outputs, the state
    and the convolution tail are the unpadded prompt's."""
    p = _params()
    x = _x(t, seed=n)
    live = (jnp.arange(t) < n)[None]
    y, s, tail = _mix(x, p, live=live)
    y_ref, s_ref = ref.gdn_mixer(x[0, :n], p, _cfg())
    np.testing.assert_allclose(y[0, :n], y_ref, atol=TOL)
    np.testing.assert_allclose(s[0], s_ref, atol=TOL)
    _, _, tail_ref = _mix(x[:, :n], p)
    np.testing.assert_allclose(tail, tail_ref, atol=TOL)


@pytest.mark.parametrize("a,b", [(10, 1), (3, 1), (64, 30), (70, 64),
                                 (1, 1), (130, 5)])
def test_a_carried_state_continues_the_sequence(a, b):
    """a positions, then b more from the state and tail they left (one more:
    the step form) equal a + b at once."""
    p = _params()
    x = _x(a + b, seed=a + b)
    whole, s_whole, tail_whole = _mix(x, p)
    _, s, tail = _mix(x[:, :a], p)
    y, s, tail = _mix(x[:, a:], p, state=(s, tail))
    np.testing.assert_allclose(y, whole[:, a:], atol=5 * TOL)
    np.testing.assert_allclose(s, s_whole, atol=5 * TOL)
    np.testing.assert_allclose(tail, tail_whole, atol=TOL)


@pytest.mark.parametrize("n", [48, 20, 33])
def test_a_long_prompt_runs_a_block_of_positions_at_a_time(n, monkeypatch):
    """Past twice ``SEQ_BLOCK`` positions the mixer takes the prompt block
    after block from the state and tail the block before left; a block of
    pad rows hands on what it was given."""
    p = _params()
    x = _x(48, seed=n, b=2)
    live = jnp.stack([jnp.arange(48) < n, jnp.arange(48) < 48])
    want = _mix(x, p, live=live)
    monkeypatch.setattr(gdn, "SEQ_BLOCK", 16)
    got = _mix(x, p, live=live)
    for a, b in zip(got[1:], want[1:]):         # state and tail
        np.testing.assert_allclose(a, b, atol=5 * TOL)
    keep = np.asarray(live)                     # a pad row's output is no one's
    np.testing.assert_allclose(np.asarray(got[0])[keep],
                               np.asarray(want[0])[keep], atol=5 * TOL)
    y_ref, s_ref = ref.gdn_mixer(x[0, :n], p, _cfg())
    np.testing.assert_allclose(got[0][0, :n], y_ref, atol=5 * TOL)
    np.testing.assert_allclose(got[1][0], s_ref, atol=5 * TOL)


@pytest.mark.parametrize("t", [5, 70])
def test_one_position_steps_are_the_recurrence(t):
    """The decode form all the way: t steps of one position, two rows at
    their own pace (the second is live every other step)."""
    p = _params()
    x = _x(t, seed=t, b=2)
    s = jnp.zeros((2, 2 * HK, DK, DK))
    tail = jnp.zeros((2, TAPS - 1, 4 * HK * DK))
    ys = []
    for i in range(t):
        live = jnp.asarray([[True], [i % 2 == 0]])
        y, s, tail = _mix(x[:, i:i + 1], p, live=live, state=(s, tail))
        ys.append(y[:, 0])
    ys = jnp.stack(ys, axis=1)
    y_ref, s_ref = ref.gdn_mixer(x[0], p, _cfg())
    np.testing.assert_allclose(ys[0], y_ref, atol=TOL)
    np.testing.assert_allclose(s[0], s_ref, atol=TOL)
    y_ref, s_ref = ref.gdn_mixer(x[1, ::2], p, _cfg())
    np.testing.assert_allclose(ys[1, ::2], y_ref, atol=TOL)
    np.testing.assert_allclose(s[1], s_ref, atol=TOL)


def test_a_chunks_decay_may_leave_float32_range():
    """g = -8 a position: a chunk's cumulated log-decay is -512 and
    exp(+512) has no float32; the scan forms differences only."""
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    h = 2 * HK
    q, k, v = (jax.random.normal(key[i], (1, 128, h, DK)) for i in range(3))
    g = jnp.full((1, 128, h, 1), -8.0)
    beta = jax.nn.sigmoid(jax.random.normal(key[3], (1, 128, h)))
    o, s = kda.kda_scan(q, k, v, g, beta, jnp.zeros((1, h, DK, DK)))
    o_ref, s_ref = ref.gdn_recurrence(q[0], k[0], v[0], g[0, :, :, 0],
                                      beta[0])
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o[0], o_ref, atol=TOL)
    np.testing.assert_allclose(s[0], s_ref, atol=TOL)


# ---- (b) the gradient --------------------------------------------------------
@pytest.mark.parametrize("t", [20, 100])
def test_the_scans_gradient_is_the_recurrences(t):
    p = _params()
    x = _x(t, seed=t)
    w = jax.random.normal(jax.random.PRNGKey(5), (t, D))

    def ours(p, x):
        return jnp.sum(_mix(x, p)[0][0] * w)

    def plain(p, x):
        return jnp.sum(ref.gdn_mixer(x[0], p, _cfg())[0] * w)

    got = jax.grad(ours, argnums=(0, 1))(p, x)
    want = jax.grad(plain, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(b).max()) + 1e-6
        np.testing.assert_allclose(a / scale, b / scale, atol=1e-4)


# ---- (c) a scalar decay's products are matmuls -------------------------------
def _shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.update(tuple(v.aval.shape) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, out)
    return out


@pytest.mark.parametrize("width,expected", [(1, False), (DK, True)])
def test_no_chunk_by_chunk_by_channel_product_for_a_scalar_decay(width,
                                                                 expected):
    """The per-channel scan builds [c, c, dk] decay products a head; the
    scalar-gate scan has none in its jaxpr: a [c, c] decay and dot
    products."""
    h, t, c = 2, 128, kda.CHUNK
    args = [jnp.zeros((1, t, h, DK))] * 3 + [
        jnp.zeros((1, t, h, width)), jnp.zeros((1, t, h)),
        jnp.zeros((1, h, DK, DK))]
    shapes = _shapes(jax.make_jaxpr(kda.kda_scan)(*args).jaxpr, set())
    assert any(s[-3:] == (c, c, DK) for s in shapes) == expected
    assert any(s[-2:] == (c, c) for s in shapes)


# ---- (d) the per-channel form is the parent's --------------------------------
def _parent_kda_scan(q, k, v, g, beta, state):
    """``kda_scan`` as PR 38 had it, line for line."""
    hi = lax.Precision.HIGHEST
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(kda.CHUNK, t)
    pad = -t % c
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    n = (t + pad) // c

    def chunks(a):
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 3, 2)

    keep = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    eye = jnp.eye(c, dtype=jnp.float32)

    def mm(spec, x, y):
        return jnp.einsum(spec, x, y, precision=hi)

    def step(s, xs):
        qc, kc, vc, gc, bc = xs
        cum = jnp.cumsum(gc, axis=2)
        rel = jnp.where(keep[:, :, None],
                        cum[:, :, :, None, :] - cum[:, :, None, :, :],
                        -jnp.inf)
        kd = kc[:, :, None, :, :] * jnp.exp(rel)
        a = jnp.sum(kc[:, :, :, None, :] * kd, axis=-1)
        qk = jnp.sum(qc[:, :, :, None, :] * kd, axis=-1)
        into = jnp.exp(cum)
        rhs = bc[..., None] * (vc - mm("bhtk,bhkv->bhtv", kc * into, s))
        w = mm("bhti,bhiv->bhtv", kda._unit_lower_inverse(
            bc[..., None] * jnp.where(strict, a, 0.0), eye, mm), rhs)
        o = mm("bhtk,bhkv->bhtv", qc * into, s) + mm(
            "bhti,bhiv->bhtv", qk, w)
        last = cum[:, :, -1:, :]
        s = jnp.swapaxes(jnp.exp(last), 2, 3) * s + mm(
            "bhik,bhiv->bhkv", kc * jnp.exp(last - cum), w)
        return s, o

    state, o = lax.scan(step, state,
                        tuple(chunks(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)
    return o.reshape(b, n * c, h, dv)[:, :t], state


@pytest.mark.parametrize("t", [7, 64, 200])
def test_per_channel_scan_is_bit_for_bit_the_parents(t):
    """Ling's inputs (``tests/test_ling_hybrid.py``: 4 heads of 16, gates in
    (-5, 0) per channel): the scan that now also takes a scalar decay
    returns the very numbers the parent's did."""
    h, dk = 4, 16
    key = jax.random.split(jax.random.PRNGKey(t), 6)
    q, k, v = (jax.random.normal(key[i], (1, t, h, dk)) for i in range(3))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(key[3], (1, t, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (1, t, h)))
    s0 = jax.random.normal(key[5], (1, h, dk, dk))
    got = jax.jit(kda.kda_scan)(q, k, v, g, beta, s0)
    want = jax.jit(_parent_kda_scan)(q, k, v, g, beta, s0)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scalar_decay_equals_the_per_channel_scan_of_its_broadcast():
    """One decay a head is the per-channel recurrence with every channel
    the same: the two branches of the chunk step agree."""
    h, t = 4, 150
    key = jax.random.split(jax.random.PRNGKey(1), 5)
    q, k, v = (jax.random.normal(key[i], (2, t, h, DK)) for i in range(3))
    q, k = kda.l2norm(q), kda.l2norm(k)     # as the mixers hand them over
    g = -jax.nn.softplus(jax.random.normal(key[3], (2, t, h, 1)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (2, t, h)))
    s0 = jnp.zeros((2, h, DK, DK))
    o1, s1 = kda.kda_scan(q, k, v, g, beta, s0)
    o2, s2 = kda.kda_scan(q, k, v, jnp.broadcast_to(g, q.shape), beta, s0)
    np.testing.assert_allclose(o1, o2, atol=TOL)
    np.testing.assert_allclose(s1, s2, atol=TOL)
