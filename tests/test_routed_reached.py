"""The reached form of the routed experts (``pallas/reached_experts.py``
through ``routed_experts.routed_ffn``): the experts that received a live
pair, fetched once each, against the dense form (every expert on every
row) — on the CPU through the Pallas interpreter. The two differ in the
order of the float32 sum over experts only.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import routed_experts
from deeplearning4j_tpu.pallas import reached_experts as kernel

N, D, F, E, K, HELD = 16, 256, 128, 32, 4, 8
TOL = 2e-6      # of the largest output: float32 accumulation


def _weights(dtype, held=HELD, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple((jax.random.normal(k, shape) * 0.05).astype(dtype)
                 for k, shape in zip(keys, ((held, D, F), (held, D, F),
                                            (held, F, D))))


def _rows(seed=1, n=N):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, D), jnp.bfloat16)


def _routing(case, first, seed=2):
    """``(experts [N, K] router indices, weights, live)`` of a case."""
    rng = np.random.default_rng(seed)
    experts = np.stack([rng.permutation(E)[:K] for _ in range(N)])
    live = np.ones(N, bool)
    if case == "some_rows_dead":
        live = np.arange(N) % 3 != 0
    elif case == "no_expert_reached":
        live[:] = False
    elif case == "none_of_the_share_chosen":
        experts = (experts % (E - HELD) + first + HELD) % E
    elif case == "every_expert_reached":
        experts[:, 0] = first + np.arange(N) % HELD
    elif case == "one_expert_takes_every_pair":
        experts[:] = (first + HELD + 1 + np.arange(K)) % E
        experts[:, 1] = first + 5
    weights = rng.random((N, K)).astype(np.float32)
    return jnp.asarray(experts, jnp.int32), jnp.asarray(weights), \
        jnp.asarray(live)


def _both(x, experts, weights, live, mats, first, blocks=None):
    cast = lambda w: w.astype(x.dtype)
    weights = jnp.where(live[:, None], weights, 0.0)
    local = experts - first
    held = mats[0].shape[0]
    load = jnp.sum(live[:, None, None]
                   & (local[..., None] == jnp.arange(held)),
                   axis=(0, 1), dtype=jnp.int32)
    dense = routed_experts._dense_experts(x, weights, local,
                                          *map(cast, mats))
    reached = kernel.reached_experts(
        x, routed_experts._combine(weights, local, held), load, *mats,
        blocks=blocks, interpret=True)
    return dense, reached, load


@pytest.mark.parametrize("store", ["float32", "bfloat16"])
@pytest.mark.parametrize("first", [0, 8])
@pytest.mark.parametrize("case", [
    "as_routed", "some_rows_dead", "no_expert_reached",
    "none_of_the_share_chosen", "every_expert_reached",
    "one_expert_takes_every_pair"])
def test_reached_form_is_the_dense_form(case, first, store):
    experts, weights, live = _routing(case, first)
    dense, reached, load = _both(_rows(), experts, weights, live,
                                 _weights(store), first)
    assert reached.shape == dense.shape and reached.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(dense)))
    if case in ("no_expert_reached", "none_of_the_share_chosen"):
        assert int(load.sum()) == 0
        assert not np.asarray(reached).any() and scale == 0.0
    else:
        assert scale > 0.01
        if case == "every_expert_reached":
            assert int((load > 0).sum()) == HELD
        if case == "one_expert_takes_every_pair":
            assert np.flatnonzero(np.asarray(load)).tolist() == [5]
        np.testing.assert_allclose(reached, dense, rtol=0, atol=TOL * scale)
    dead = ~np.asarray(live)
    assert not np.asarray(reached)[dead].any()


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)])
def test_block_sizes_change_the_order_of_the_sum_only(blocks):
    experts, weights, live = _routing("some_rows_dead", 0)
    dense, reached, _ = _both(_rows(), experts, weights, live,
                              _weights("float32"), 0, blocks)
    np.testing.assert_allclose(
        reached, dense, rtol=0, atol=TOL * float(jnp.max(jnp.abs(dense))))


@pytest.mark.parametrize("n", [1, 5, 24])
def test_row_counts_off_the_sublane_tile(n):
    rng = np.random.default_rng(n)
    experts = jnp.asarray(rng.integers(0, HELD, (n, K)), jnp.int32)
    weights = jnp.asarray(rng.random((n, K)), jnp.float32)
    dense, reached, _ = _both(_rows(n=n), experts, weights,
                              jnp.ones(n, bool), _weights("float32"), 0)
    np.testing.assert_allclose(
        reached, dense, rtol=0, atol=TOL * float(jnp.max(jnp.abs(dense))))


@pytest.mark.parametrize("load", [
    [0, 0, 0, 0, 0, 0, 0, 0], [3, 0, 0, 1, 0, 0, 0, 9],
    [0, 1, 0, 0, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1, 1, 1],
    [0, 0, 0, 0, 0, 0, 0, 64]])
def test_work_list_holds_each_reached_expert_once(load):
    count, ids = kernel.work_list(jnp.asarray(load, jnp.int32))
    want = np.flatnonzero(load).tolist()
    assert count.tolist() == [len(want)]
    assert ids.dtype == jnp.int32 and ids.shape == (len(load),)
    assert ids[:len(want)].tolist() == want


@pytest.mark.parametrize("n,d,f,store,fits", [
    (64, 2560, 768, "float32", True),       # Ling's decode step
    (64, 2560, 768, "bfloat16", True),
    (8, 2048, 1024, "float32", True),       # OLMoE's widths, 8 rows
    (64, 2304, 896, "float32", True),       # Mellum2's decode step
    (4, 64, 32, "float32", False),          # widths off the lane tile
    (512, 2560, 768, "float32", False)])    # rows that do not fit VMEM
def test_blocks_fit_the_widths_or_the_kernel_does_not_apply(n, d, f, store,
                                                            fits):
    blocks = kernel.expert_blocks(n, d, f, store)
    assert (blocks is not None) == fits
    if fits:
        size = jnp.dtype(store).itemsize
        assert d % blocks[0] == 0 and f % blocks[1] == 0
        assert blocks[0] % 128 == 0 and blocks[1] % 128 == 0
        assert max(blocks[0] * f, blocks[1] * d) * size <= kernel._BLOCK_BYTES


# ---------------------------------------------------------------------------
# the choice: the rows of the trace, ``train`` and the backend
# ---------------------------------------------------------------------------
def _trace(n, k, e, held, *, d=256, f=128, train=False, backend="interpret",
           groups=None, monkeypatch=None):
    """The jaxpr of ``routed_ffn`` at these shapes, nothing computed."""
    if backend is not None:
        monkeypatch.setattr(routed_experts, "_kernel_backend",
                            lambda: backend)
    f32 = jnp.float32
    p = {"router": jax.ShapeDtypeStruct((d, e), f32),
         "w_gate": jax.ShapeDtypeStruct((held, d, f), f32),
         "w_up": jax.ShapeDtypeStruct((held, d, f), f32),
         "w_down": jax.ShapeDtypeStruct((held, f, d), f32)}
    x = jax.ShapeDtypeStruct((n, d), jnp.bfloat16)
    return str(jax.make_jaxpr(
        lambda x, p: routed_experts.routed_ffn(
            x, p, experts_per_token=k, cast=lambda w: w.astype(x.dtype),
            groups=groups, train=train)[0])(x, p))


def _dense_product(held, n, f):
    return f"f32[{held},{n},{f}]"


LING = dict(n=64, k=8, e=512, held=64, groups=(8, 4, 2.5))


@pytest.mark.parametrize("why,kw", [
    ("lings_decode_step", LING),
    ("olmoes_decode_step_32_slots", dict(n=32, k=8, e=64, held=64)),
    ("mellum2s_decode_step_64_slots", dict(n=64, k=8, e=64, held=64)),
    ("olmoes_smallest_prefill_rung", dict(n=16, k=8, e=64, held=64))])
def test_a_trace_the_kernel_holds_takes_the_reached_form(why, kw,
                                                         monkeypatch):
    text = _trace(monkeypatch=monkeypatch, **kw)
    assert text.count("pallas_call") == 1           # one a layer
    assert _dense_product(kw["held"], kw["n"], 128) not in text
    # the matrices go to the kernel as stored: no compute-dtype copy
    assert f"bf16[{kw['held']},256,128]" not in text


@pytest.mark.parametrize("why,kw", [
    ("rows_over_the_bound",
     dict(n=2 * routed_experts.REACHED_MAX_ROWS, k=8, e=64, held=64)),
    ("rows_over_the_bound_of_a_share",
     dict(LING, n=2 * routed_experts.REACHED_MAX_ROWS)),
    ("a_trace_that_takes_a_gradient", dict(LING, train=True)),
    ("no_mosaic_backend", dict(LING, backend=None)),
    ("widths_off_the_lane_tile", dict(LING, d=64, f=32))])
def test_every_other_trace_is_the_one_it_was(why, kw, monkeypatch):
    text = _trace(monkeypatch=monkeypatch, **kw)
    assert "pallas_call" not in text
    n, held, f = kw["n"], kw["held"], kw.get("f", 128)
    assert _dense_product(held, n, f) in text
    # and it is the dense form's own jaxpr, with the kernel out of reach
    assert text == _trace(monkeypatch=monkeypatch, **dict(kw, backend=None))


def test_the_cpu_default_keeps_the_dense_form():
    assert routed_experts._kernel_backend() is None
    text = _trace(**dict(LING, backend=None))
    assert "pallas_call" not in text and _dense_product(64, 64, 128) in text


def test_the_bound_is_in_rows_and_covers_every_decode_program():
    """The rule reads the rows of the trace: both cells whose slots' pairs
    an expert (4.0, 8.0) kept them on the dense form are under it, and it
    asks the kernel for no more rows than its VMEM holds."""
    olmoe_slots, mellum2_slots = 32, 64
    assert max(olmoe_slots, mellum2_slots) <= routed_experts.REACHED_MAX_ROWS
    assert routed_experts.REACHED_MAX_ROWS <= kernel._MAX_ROWS
    assert kernel.expert_blocks(routed_experts.REACHED_MAX_ROWS, 2304, 896,
                                "float32") == (384, 128)


def _rule_before_pr46(x, w_gate, train, *, k, num_experts):
    """``N k / E < 2``, the slots' pairs an expert: what chose the form
    until PR 46."""
    n = x.shape[0]
    if (train or n * k >= 2 * num_experts
            or routed_experts._kernel_backend() is None):
        return None
    return kernel.expert_blocks(n, x.shape[1], w_gate.shape[2], w_gate.dtype)


@pytest.mark.parametrize("why,kw", [
    ("ling_64_slots_8_of_512_64_held", LING),
    ("qwen3next_64_slots_10_of_512_128_held",
     dict(n=64, k=10, e=512, held=128)),
    ("glm_16_slots_8_of_256_8_held",
     dict(n=16, k=8, e=256, held=8, groups=(1, 1, 2.5))),
    ("gigachat_a_round_of_16_slots_8_of_256_8_held",
     dict(n=32, k=8, e=256, held=8, groups=(8, 4, 2.5)))])
def test_the_share_cells_decode_programs_did_not_move(why, kw, monkeypatch):
    """The four cells that were under the old bound already: their decode
    shapes trace to the same jaxpr under the old rule and the new."""
    new = _trace(monkeypatch=monkeypatch, **kw)
    assert new.count("pallas_call") == 1
    monkeypatch.setattr(
        routed_experts, "_reached_blocks",
        functools.partial(_rule_before_pr46, k=kw["k"], num_experts=kw["e"]))
    assert new == _trace(monkeypatch=monkeypatch, **kw)


@pytest.mark.parametrize("store", ["float32", "bfloat16"])
def test_mellum2s_decode_step_is_the_same_rows_in_either_form(store,
                                                              monkeypatch):
    """64 slots, 8 of 64 experts, 12 slots live, no shared expert: the form
    the decode program had (dense) against the one it takes now."""
    n, e, k, n_live = 64, 64, 8, 12
    p = routed_experts.init_experts(jax.random.PRNGKey(6), D, F, e,
                                    jnp.float32)
    p = {name: (v.astype(store) if name.startswith("w_") else v)
         for name, v in p.items()}
    x = _rows(seed=7, n=n)
    live = np.zeros(n, bool)
    live[np.random.default_rng(8).permutation(n)[:n_live]] = True
    live = jnp.asarray(live)
    run = lambda: routed_experts.routed_ffn(
        x, p, experts_per_token=k, cast=lambda w: w.astype(x.dtype),
        live=live)
    y_dense, info_dense = run()
    monkeypatch.setattr(routed_experts, "_kernel_backend",
                        lambda: "interpret")
    y, info = run()
    assert int(info_dense["read"]) == e
    assert int(info["read"]) == int((info["load"] > 0).sum()) < e
    assert int(info["load"].sum()) == n_live * k
    np.testing.assert_array_equal(info["experts"], info_dense["experts"])
    np.testing.assert_allclose(
        y.astype(jnp.float32), y_dense.astype(jnp.float32), rtol=0,
        atol=2.0 ** -7 * float(jnp.max(jnp.abs(y_dense.astype(jnp.float32)))))
    assert not np.asarray(y.astype(jnp.float32))[~np.asarray(live)].any()
    # and the kernel's float32 sum before the output is rounded
    dense32, reached32, _ = _both(
        x, info["experts"], info["weights"], live,
        (p["w_gate"], p["w_up"], p["w_down"]), 0)
    np.testing.assert_allclose(
        reached32, dense32, rtol=0,
        atol=TOL * float(jnp.max(jnp.abs(dense32))))


@pytest.mark.parametrize("store", ["float32", "bfloat16"])
def test_routed_ffn_gives_the_same_rows_in_either_form(store, monkeypatch):
    """The whole layer — group-limited router, a share with ``first`` > 0,
    dead rows, the shared expert — with the kernel allowed and not."""
    first, n = 8, 12
    p = routed_experts.init_experts(
        jax.random.PRNGKey(4), D, F, E, jnp.float32, held=HELD, bias=True,
        shared_width=F)
    p = {k: (v.astype(store) if k.startswith("w_") else v)
         for k, v in p.items()}
    x = _rows(seed=5, n=n)
    live = jnp.arange(n) % 4 != 1
    run = lambda: routed_experts.routed_ffn(
        x, p, experts_per_token=K, cast=lambda w: w.astype(x.dtype),
        live=live, groups=(4, 2, 2.5), first=first)
    y_dense, info_dense = run()
    monkeypatch.setattr(routed_experts, "_kernel_backend",
                        lambda: "interpret")
    y, info = run()
    assert int(info_dense["read"]) == HELD
    assert int(info["read"]) == int((info["load"] > 0).sum()) < HELD
    np.testing.assert_array_equal(info["experts"], info_dense["experts"])
    np.testing.assert_array_equal(info["load"], info_dense["load"])
    np.testing.assert_allclose(
        y.astype(jnp.float32), y_dense.astype(jnp.float32), rtol=0,
        atol=2.0 ** -7 * float(jnp.max(jnp.abs(y_dense.astype(jnp.float32)))))
    assert not np.asarray(y.astype(jnp.float32))[~np.asarray(live)].any()
