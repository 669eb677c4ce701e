"""``pallas/delta_step.py`` (interpreted here) against ``kda.kda_step``, the
plain form it is held to: a live row's output and state to float32
tolerance, a dead row's state bit for bit and its output zero; the mixers
and the serving decode step through the kernel against the plain step; and
the decode program's jaxpr holds no other op that makes a whole state.

float32 at ``highest`` on both sides: the kernel sums on the VPU, the plain
step through a dot, so they differ in the order of sums only.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import test_gdn as small_gdn  # noqa: E402
import test_ling_hybrid as small_ling  # noqa: E402
import test_qwen3next as small_qwen  # noqa: E402
from deeplearning4j_tpu.models import kda  # noqa: E402
from deeplearning4j_tpu.pallas.delta_step import (  # noqa: E402
    delta_step, step_heads)
from deeplearning4j_tpu.pallas.reached_experts import work_list  # noqa: E402
from deeplearning4j_tpu.serving import SlotKVCache  # noqa: E402
from deeplearning4j_tpu.serving import engine as eng  # noqa: E402

TOL = 2e-6
PATTERNS = {
    "all": lambda b: np.ones(b, bool),
    "none": lambda b: np.zeros(b, bool),
    "prefix": lambda b: np.arange(b) < b // 2,
    "scattered": lambda b: np.arange(b) % 3 == 1,
    "one": lambda b: np.arange(b) == b - 2,
}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _operands(b, h, dk, dv, per_channel, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda.l2norm(jax.random.normal(ks[0], (b, h, dk))) * dk ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (b, h, dk)))
    v = jax.random.normal(ks[2], (b, h, dv))
    g = -3.0 * jax.random.uniform(ks[3], (b, h, dk if per_channel else 1))
    beta = jax.random.uniform(ks[4], (b, h))
    state = jax.random.normal(ks[5], (b, h, dk, dv))   # carried, not zero
    return q, k, v, g, beta, state


def _plain(q, k, v, g, beta, state, live):
    g, beta = kda.mask_dead(g[:, None], beta[:, None], live[:, None])
    return kda.kda_step(q, k, v, g[:, 0], beta[:, 0], state)


# ---- (a) the kernel is kda_step on the live rows -----------------------------
@pytest.mark.parametrize("slots", [4, 11])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("decay", ["channel", "head"])
def test_the_kernel_is_the_plain_step_on_the_live_rows(decay, pattern, slots):
    """16 heads in two blocks of 8, dk != dv, a carried state."""
    ops = _operands(slots, 16, 8, 16, decay == "channel", seed=slots)
    live = PATTERNS[pattern](slots)
    want_o, want_s = _plain(*ops, jnp.asarray(live))
    got_o, got_s = jax.jit(functools.partial(delta_step, interpret=True))(
        *ops, jnp.asarray(live))
    got_o, got_s = np.asarray(got_o), np.asarray(got_s)
    np.testing.assert_allclose(got_o[live], np.asarray(want_o)[live],
                               atol=TOL)
    np.testing.assert_allclose(got_s[live], np.asarray(want_s)[live],
                               atol=TOL)
    # a slot that owes nothing: its matrix bit for bit, its output zero
    np.testing.assert_array_equal(got_s[~live], np.asarray(ops[5])[~live])
    assert not got_o[~live].any()
    if live.any():
        assert np.abs(got_s[live] - np.asarray(ops[5])[live]).max() > 1e-3


@pytest.mark.parametrize("heads,group", [(16, 16), (16, 8), (4, 4), (6, 6),
                                         (32, 32), (32, 8)])
def test_any_split_of_the_heads_gives_the_same_rows(heads, group):
    """The block is a matter of speed: one block a slot, or several."""
    ops = _operands(5, heads, 16, 16, False, seed=heads)
    live = jnp.asarray([True, False, True, True, False])
    want_o, want_s = _plain(*ops, live)
    got_o, got_s = delta_step(*ops, live, heads=group, interpret=True)
    rows = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got_o)[rows],
                               np.asarray(want_o)[rows], atol=TOL)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=TOL)
    assert heads % step_heads(heads) == 0
    assert step_heads(heads) in (heads, 8, 16, 24, 32)


def test_no_live_argument_means_every_row():
    ops = _operands(3, 8, 16, 16, True)
    want_o, want_s = kda.kda_step(*ops)
    got_o, got_s = delta_step(*ops, interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=TOL)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=TOL)


def test_heads_that_do_not_split_are_refused():
    with pytest.raises(ValueError, match="do not split"):
        delta_step(*_operands(2, 6, 8, 8, False), heads=4, interpret=True)


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_the_work_list_is_the_live_slots_lowest_first(pattern):
    live = PATTERNS[pattern](9)
    count, idx = work_list(jnp.asarray(live))
    n = int(count[0])
    assert n == live.sum() and idx.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(idx)[:n], np.nonzero(live)[0])
    assert not np.asarray(idx)[n:].any()


@pytest.mark.parametrize("decay", ["channel", "head"])
def test_ten_chained_steps_are_ten_plain_steps(decay):
    """The state carried from step to step, three rows at their own pace:
    the kernel's tenth state is the plain step's."""
    q, k, v, g, beta, state = _operands(3, 8, 16, 16, decay == "channel")
    want = got = state
    step = jax.jit(functools.partial(delta_step, interpret=True))
    for i in range(10):
        live = jnp.asarray([True, i % 2 == 0, i < 4])
        roll = functools.partial(jnp.roll, shift=i, axis=1)   # other heads
        ops = tuple(roll(a) for a in (q, k, v, g, beta))
        want_o, want = _plain(*ops, want, live)
        got_o, got = step(*ops, got, live)
        rows = np.asarray(live)
        np.testing.assert_allclose(np.asarray(got_o)[rows],
                                   np.asarray(want_o)[rows], atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---- (b) ten decode steps of the small models --------------------------------
def _ten_steps(mix, x, state):
    """Ten positions, three rows at their own pace: ``mix(x_t, live,
    state) -> (y, S, tail)``; the first row owes a token every step, the
    second every other one, the third none after the fourth."""
    ys = []
    for i in range(10):
        live = jnp.asarray([[True], [i % 2 == 0], [i < 4]])
        y, *state = mix(x[:, i:i + 1], live, tuple(state))
        ys.append(np.where(np.asarray(live)[:, :, None], y, 0.0))
    return np.concatenate(ys, axis=1), state


@pytest.mark.parametrize("model", ["gdn", "kda"])
def test_ten_steps_of_a_mixer_through_the_kernel_are_the_plain_loop(
        model, monkeypatch):
    """``kernel=True`` against the mixer's plain loop; for KDA with the
    branch that keeps a decay a channel off the kernel taken out
    (``_recur_by_the_kernel``)."""
    if model == "kda":
        monkeypatch.setattr(kda, "recur", functools.partial(
            _recur_by_the_kernel, kda.recur))
    if model == "gdn":
        p = small_gdn._params()
        x = small_gdn._x(10, seed=3, b=3)
        hv, dk = 2 * small_gdn.HK, small_gdn.DK
        mix = functools.partial(small_gdn._mix, p=p)
        width = 4 * small_gdn.HK * dk
    else:
        lm = small_ling._lm()
        p = lm.params["blocks"][0]["kda"]
        x = jax.random.normal(jax.random.PRNGKey(5), (3, 10, small_ling.D))
        hv, dk = small_ling.H, small_ling.DK
        mix = functools.partial(kda.kda_mixer, p=p, num_heads=hv, lower=-5.0)
        width = 3 * hv * dk
    start = [0.1 * jax.random.normal(jax.random.PRNGKey(7), (3, hv, dk, dk)),
             jnp.zeros((3, 3, width))]
    want_y, want = _ten_steps(
        lambda xt, live, st: mix(xt, live=live, state=st), x, start)
    got_y, got = _ten_steps(
        lambda xt, live, st: mix(xt, live=live, state=st, kernel=True),
        x, start)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _decode_ten(lm, pool_kernel, slots=3, max_len=48):
    rng = np.random.default_rng(2)
    kv = jax.tree_util.tree_map(
        lambda a: jnp.asarray(0.3 * rng.normal(size=a.shape), a.dtype),
        SlotKVCache(lm, slots, max_len).state)
    step = jax.jit(functools.partial(eng._decode_step_body, lm,
                                     pool_kernel=pool_kernel))
    cursors = np.asarray([3, 20, 9])
    logits = []
    for i in range(10):
        live = np.asarray([True, i % 2 == 0, i < 4])
        toks = jnp.asarray(rng.integers(1, 256, slots), jnp.int32)
        out, kv = step(lm.params, kv, toks, jnp.asarray(cursors), live=live)
        logits.append(np.asarray(out)[live])
        cursors = cursors + live
    return np.concatenate(logits), kv


@pytest.mark.parametrize("model", ["ling", "qwen3next"])
def test_ten_decode_steps_through_the_kernel_are_the_plain_steps(model):
    """The serving decode step of the two small hybrid models: the program a
    chip runs (the kernel where ``recur`` takes it, interpreted here) and
    with ``pool_kernel=False`` (``kda_step``: the program under a mesh)."""
    lm = (small_ling if model == "ling" else small_qwen)._lm()
    want, want_kv = _decode_ten(lm, False)
    got, got_kv = _decode_ten(lm, None)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(got_kv),
                    jax.tree_util.tree_leaves(want_kv)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


# ---- (c) the decode program makes no second copy of a state ------------------
def _recur_by_the_kernel(recur, q, k, v, g, beta, state, s0, names,
                         live=None, kernel=False):
    """``kda.recur`` with the kernel's one position whatever the decay."""
    if not kernel:
        return recur(q, k, v, g, beta, state, s0, names, live, kernel)
    assert state is not None and q.shape[1] == 1
    with kda.scope(names[0]):
        o, s = kda.delta_step(*(a[:, 0] for a in (q, k, v, g, beta)), s0,
                              live[:, 0])
    return o[:, None], s


def _state_makers(jaxpr, shape, out):
    """Every equation of ``jaxpr`` (and of the jaxprs inside it, but a
    kernel's body: its values are blocks) with an output of ``shape``, that
    is not a wrapper round others."""
    for eqn in jaxpr.eqns:
        subs = ([] if eqn.primitive.name == "pallas_call"
                else list(jax.core.jaxprs_in_params(eqn.params)))
        for sub in subs:
            _state_makers(sub, shape, out)
        if not subs and any(getattr(v.aval, "shape", None) == shape
                            for v in eqn.outvars):
            out.append(eqn)
    return out


@pytest.mark.parametrize("model", ["ling", "qwen3next"])
def test_the_state_meets_the_kernel_and_nothing_else_makes_one(model,
                                                               monkeypatch):
    """In the serving decode program a ``[slots, H, dk, dv]`` array comes
    out of the kernel's ``pallas_call``, aliased to the state that went in,
    and out of no other equation: no decay over every slot, no
    dynamic-update-slice, no select between an old and a new state. Under
    ``pool_kernel=False`` the plain step's multiply and add are there.

    A decay a channel (Ling's KDA layers) stays on the plain step until
    ``kda_roofline`` can take the kernel (``kda.recur``): its program is
    held to the rule with that one branch taken out, and as it is it holds
    no kernel."""
    small = small_ling if model == "ling" else small_qwen
    lm = small._lm()
    slots = 5
    cache = SlotKVCache(lm, slots, 32)
    if model == "ling":
        shipped = jax.make_jaxpr(functools.partial(
            eng._serve_decode_loop_impl, lm, eng._row_sampler(0.0, None)))(
                lm.params, cache.state, cache.loop)
        assert "pallas_call" not in str(shipped)
        monkeypatch.setattr(kda, "recur", functools.partial(
            _recur_by_the_kernel, kda.recur))
    shape = tuple(cache.state["kda"][0].shape)
    assert len(shape) == 4 and shape[0] == slots

    def makers(**kw):
        fn = functools.partial(eng._serve_decode_loop_impl, lm,
                               eng._row_sampler(0.0, None), **kw)
        jaxpr = jax.make_jaxpr(fn)(lm.params, cache.state, cache.loop)
        return _state_makers(jaxpr.jaxpr, shape, [])

    eqns = makers()
    assert [e.primitive.name for e in eqns] == ["pallas_call"] * len(
        cache.state["kda"])
    for eqn in eqns:
        # the state is the last operand and the second result
        aliases = dict(eqn.params["input_output_aliases"])
        assert aliases == {len(eqn.invars) - 1: 1}
        assert tuple(eqn.invars[-1].aval.shape) == shape
        assert tuple(eqn.outvars[1].aval.shape) == shape
    plain = {e.primitive.name for e in makers(pool_kernel=False)}
    assert "pallas_call" not in plain and {"mul", "add"} <= plain
