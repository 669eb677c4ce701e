"""The sorted form of the routed experts on one chip's share
(``routed_experts._grouped_experts``): the (token, expert) pairs held here,
in passes of a static number of sorted rows, against a plain loop over every
pair kept in this file -- and the traces that must not gain a loop: every
expert held, a trace that takes a gradient, the dense form, the reached form.

Small widths, float32, on the CPU; ``PASS_TILE`` is lowered so that a pass is
a few dozen rows and a block of a hundred tokens needs several.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import routed_experts

D, F, E, K, HELD = 64, 32, 32, 4, 8
# float32 on both sides: the passes and the loop differ in the order of a
# row's sum over its experts only (tests/test_olmoe.py holds the sorted form
# to its reference by the same bound)
REL = 1e-4


@pytest.fixture(autouse=True)
def small_passes(monkeypatch):
    monkeypatch.setattr(routed_experts, "DENSE_MAX_TOKENS", 8)
    monkeypatch.setattr(routed_experts, "PASS_TILE", 8)


def _params(shared=False, bias=False, seed=0):
    return routed_experts.init_experts(
        jax.random.PRNGKey(seed), D, F, E, jnp.float32, held=HELD, bias=bias,
        shared_width=F if shared else 0, shared_gate=shared)


def _rows(n, seed=1):
    """Rows whose first number is 3: what ``_steer`` turns the router by."""
    return jax.random.normal(jax.random.PRNGKey(seed), (n, D),
                             jnp.float32).at[:, 0].set(3.0)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _all_pairs(x, p, info, live, first):
    """Every (token, expert) pair on its own: a live token's k-th choice, if
    that expert is one of the ``HELD`` from ``first``, adds its weight times
    that expert's SwiGLU of the token; the shared expert adds its own."""
    experts, weights = np.asarray(info["experts"]), np.asarray(
        info["weights"])
    y = np.zeros(x.shape, np.float64)
    for n in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = experts[n, j] - first
            if live[n] and 0 <= e < HELD:
                y[n] += weights[n, j] * np.asarray(_swiglu(
                    x[n], p["w_gate"][e], p["w_up"][e], p["w_down"][e]),
                    np.float64)
    if "shared" in p:
        sh = p["shared"]
        out = np.asarray(_swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])
                         * jax.nn.sigmoid(x @ sh["gate"])[:, None])
        y += np.where(np.asarray(live)[:, None], out, 0.0)
    return y


def _steer(p, first, to):
    """The logits of the experts held here pushed up (every token chooses
    them) or down (none does), through the rows' first number."""
    cols = jnp.arange(E)
    held = (cols >= first) & (cols < first + HELD)
    return {**p, "router": p["router"].at[0].add(jnp.where(held, to, 0.0))}


CASES = {
    # name: (rows, first, kwargs of the case)
    "even_router": (96, 0, {}),
    "every_token_chooses_held_experts": (96, 0, {"steer": 10.0}),
    "no_token_chooses_one": (96, 0, {"steer": -10.0, "shared": True}),
    "first_above_zero": (96, 16, {}),
    "first_above_zero_crowded": (96, 24, {"steer": 10.0}),
    "dead_rows": (96, 0, {"dead": True}),
    "groups": (96, 8, {"groups": (4, 2, 2.5), "bias": True}),
    "row_blocks": (128, 0, {"row_block": 32}),
    "row_blocks_crowded": (128, 8, {"row_block": 32, "steer": 10.0,
                                    "dead": True}),
    "pass_rows_does_not_divide_the_pairs": (100, 0, {"steer": 10.0}),
}


def _case(name, monkeypatch):
    n, first, kw = CASES[name]
    if "row_block" in kw:
        monkeypatch.setattr(routed_experts, "ROW_BLOCK", kw["row_block"])
    p = _params(shared=kw.get("shared", False), bias=kw.get("bias", False))
    if "steer" in kw:
        p = _steer(p, first, kw["steer"])
    live = (np.arange(n) % 5 != 2) if kw.get("dead") else np.ones(n, bool)
    run = jax.jit(functools.partial(
        routed_experts.routed_ffn, experts_per_token=K, first=first,
        groups=kw.get("groups")))
    block = kw.get("row_block", n)
    pass_rows = routed_experts._pass_rows(block, K, HELD, E, False)
    return n, first, p, jnp.asarray(live), run, block, pass_rows


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_passes_are_the_plain_sum_over_every_pair(name, monkeypatch):
    n, first, p, live, run, block, pass_rows = _case(name, monkeypatch)
    assert pass_rows < block * K            # the passes, not the one pass
    x = _rows(n)
    y, info = run(x, p, live=live)
    want = _all_pairs(x, p, info, np.asarray(live), first)
    assert np.abs(np.asarray(y, np.float64) - want).max() \
        <= REL * np.abs(want).max()
    assert not np.asarray(y)[~np.asarray(live)].any()
    # the counter: whole passes, as many as each block's pairs here need
    here = np.asarray(live)[:, None] & (
        (np.asarray(info["experts"]) >= first)
        & (np.asarray(info["experts"]) < first + HELD))
    assert int(info["load"].sum()) == here.sum()
    trips = [-(-int(rows.sum()) // pass_rows)
             for rows in here.reshape(-1, block, K)]
    assert int(info["run"]) == sum(trips) * pass_rows
    if "every_token" in name or "crowded" in name or "divide" in name:
        assert max(trips) > 1
        assert (block * K) % pass_rows or "divide" not in name
    if name == "no_token_chooses_one":
        assert int(info["run"]) == 0 and np.abs(want).max() > 0
    # and again: the same bits
    again, _ = run(x, p, live=live)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(again))


@pytest.mark.parametrize("name", ["even_router", "first_above_zero",
                                  "row_blocks", "groups"])
def test_a_rows_bits_do_not_depend_on_the_other_rows_routing(name,
                                                             monkeypatch):
    """A row's sum runs over its experts in ascending order whatever the
    passes hold beside it: the other rows re-drawn, all following row 0 (its
    experts crowded, the passes cut elsewhere, more of them), or dead."""
    n, first, p, live, run, block, pass_rows = _case(name, monkeypatch)
    x = _rows(n)
    base, info = run(x, p)
    others = x.at[8:].set(_rows(n - 8, seed=7))
    # the row that chose most experts held here, followed by every other:
    # more pairs here than one pass takes
    local = np.asarray(info["experts"]) - first
    leader = int(np.argmax(((local >= 0) & (local < HELD)).sum(axis=1)))
    crowd = x.at[8:].set(x[leader])
    runs = {int(info["run"])}
    for variant, mask in ((others, None), (crowd, None),
                          (x, jnp.arange(n) < 8)):
        y, info = run(variant, p, live=mask)
        np.testing.assert_array_equal(np.asarray(y[:8]),
                                      np.asarray(base[:8]))
        runs.add(int(info["run"]))
    assert max(runs) > min(runs) >= pass_rows   # the passes did move


def test_pass_rows_come_from_the_shapes():
    rows = functools.partial(routed_experts._pass_rows, train=False)
    tile = routed_experts.PASS_TILE
    # Qwen3-Next's row block, GigaChat's and GLM's prefill block, Ling's
    # rungs: the even router's share and a quarter, in whole tiles
    for n, k, held, e in ((4096, 10, 128, 512), (2048, 8, 8, 256),
                          (2048, 8, 64, 512), (8192, 8, 64, 512)):
        got = rows(n, k, held, e)
        assert got % tile == 0 and got < n * k
        assert 0 <= got - 1.25 * n * k * held / e < tile
    # every expert held, or so many that a quarter more is every pair
    assert rows(4096, 8, 64, 64) == 4096 * 8
    assert rows(96, 4, 28, 32) == 96 * 4
    assert routed_experts._pass_rows(4096, 10, 128, 512, True) == 40960


# ---- the traces that keep the program they had ------------------------------
def _jaxpr(n, k, e, held, *, train=False, backend=None, groups=None,
           monkeypatch):
    """``(the jaxpr of routed_ffn at these shapes, the pass_rows of every
    call of the sorted form in it)``, nothing computed, with the module's
    own thresholds."""
    monkeypatch.undo()
    monkeypatch.setattr(routed_experts, "_kernel_backend", lambda: backend)
    sort, seen = routed_experts._grouped_experts, []
    monkeypatch.setattr(
        routed_experts, "_grouped_experts",
        lambda *a, **kw: seen.append(kw["pass_rows"]) or sort(*a, **kw))
    f32, d, f = jnp.float32, 256, 128
    p = {"router": jax.ShapeDtypeStruct((d, e), f32),
         "w_gate": jax.ShapeDtypeStruct((held, d, f), f32),
         "w_up": jax.ShapeDtypeStruct((held, d, f), f32),
         "w_down": jax.ShapeDtypeStruct((held, f, d), f32)}
    x = jax.ShapeDtypeStruct((n, d), jnp.bfloat16)
    return str(jax.make_jaxpr(
        lambda x, p: routed_experts.routed_ffn(
            x, p, experts_per_token=k, cast=lambda w: w.astype(x.dtype),
            groups=groups, train=train)[0])(x, p)), seen


@pytest.mark.parametrize("why,kw", [
    ("olmoe_rung_2048_every_expert_held", dict(n=2048, k=8, e=64, held=64)),
    ("olmoe_rung_4096_every_expert_held", dict(n=4096, k=8, e=64, held=64)),
    ("a_share_in_a_trace_that_takes_a_gradient",
     dict(n=4096, k=10, e=512, held=128, train=True)),
    ("gigachat_rung_256_dense", dict(n=256, k=8, e=256, held=8,
                                     groups=(8, 4, 2.5))),
    ("gigachat_rung_512_dense", dict(n=512, k=8, e=256, held=8,
                                     groups=(8, 4, 2.5))),
    ("gigachat_rung_1024_dense", dict(n=1024, k=8, e=256, held=8,
                                      groups=(8, 4, 2.5))),
    ("olmoe_decode_reached", dict(n=32, k=8, e=64, held=64,
                                  backend="mosaic")),
    ("ling_decode_reached", dict(n=64, k=8, e=512, held=64,
                                 groups=(8, 4, 2.5), backend="mosaic")),
    ("glm_decode_reached", dict(n=16, k=8, e=256, held=8,
                                groups=(1, 1, 2.5), backend="mosaic")),
    ("gigachat_round_reached", dict(n=32, k=8, e=256, held=8,
                                    groups=(8, 4, 2.5), backend="mosaic")),
    ("qwen3next_decode_reached", dict(n=64, k=10, e=512, held=128,
                                      backend="mosaic"))])
def test_the_bypassed_traces_hold_no_loop(why, kw, monkeypatch):
    """The dense and the reached form never come to the sorted form; every
    expert held, and a trace that takes a gradient, take it in one pass
    over every pair with no ``while`` (the parent's straight line)."""
    text, passes = _jaxpr(monkeypatch=monkeypatch, **kw)
    assert "while[" not in text.split("pallas_call")[0]
    assert ("pallas_call" in text) == why.endswith("reached")
    if kw["n"] > 1024:
        assert passes == [kw["n"] * kw["k"]]
        assert text.count("ragged_dot_general[") == 3
    else:
        assert passes == [] and "ragged_dot_general[" not in text


def test_a_share_past_the_dense_form_takes_the_passes(monkeypatch):
    """The other side of the bypass: the traces the passes are for have one
    loop a layer and the three grouped matmuls once, over a pass's rows."""
    for kw in (dict(n=4096, k=10, e=512, held=128),           # Qwen3-Next
               dict(n=2048, k=8, e=256, held=8, groups=(8, 4, 2.5)),
               dict(n=8192, k=8, e=512, held=64, groups=(8, 4, 2.5))):
        text, (rows,) = _jaxpr(monkeypatch=monkeypatch, **kw)
        assert rows == routed_experts._pass_rows(
            kw["n"], kw["k"], kw["held"], kw["e"], False) < kw["n"] * kw["k"]
        assert text.count("while[") == 1
        assert text.count("ragged_dot_general[") == 3
        assert f"bf16[{rows},256]" in text
        assert f"[{kw['n'] * kw['k']},256]" not in text


def test_the_gradient_of_a_share_is_the_dense_forms(monkeypatch):
    """``train=True`` keeps the sorted form one pass with no loop, so it has
    its gradient, and that is the dense form's."""
    n, first = 24, 8
    p, x = _params(shared=True), _rows(n)
    live = jnp.arange(n) % 4 != 1

    def loss(p, x):
        y, _ = routed_experts.routed_ffn(
            x, p, experts_per_token=K, first=first, live=live, train=True)
        return jnp.sum(y * jnp.cos(jnp.arange(D)))

    assert "while" not in str(jax.make_jaxpr(jax.grad(loss))(p, x))
    got = jax.grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(routed_experts, "DENSE_MAX_TOKENS", 1024)
    want = jax.grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=0, atol=REL * scale)
    assert float(jnp.abs(got[0]["w_down"]).max()) > 0


# ---- the server books what the programs counted -----------------------------
def test_the_server_books_the_rows_run_on_the_first_token_span_and_in_stats():
    """A prefill whose rung took the sorted form hands its passes' rows over
    with its routing; the server opens a ``serve.passes`` span inside the
    ``serve.first_token`` span that read it, with the rows and the pairs they
    were run for as the attrs it is opened with (those reach a profiler
    trace), and sums both in ``stats()``. Held to the routing the server
    recorded: whole passes, as many as each layer's pairs here need. A
    decode step runs no pass and opens no such span."""
    from deeplearning4j_tpu.models.transformer import TransformerLM
    from deeplearning4j_tpu.monitor.trace import tracer
    from deeplearning4j_tpu.serving import DecodeServer

    first, held, layers = 4, 4, 2
    lm = TransformerLM(
        vocab_size=256, d_model=D, num_heads=4, num_layers=layers, d_ff=F,
        max_len=128, pos_encoding="rope", dtype_policy="float32",
        attn_impl="xla", norm="rmsnorm", num_experts=16, experts_per_token=K,
        tie_embeddings=False, seed=3,
        moe={"first": first, "held": held}).init()
    tracer().clear()
    server = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 32, 64),
                          record_routing=True)
    rng = np.random.default_rng(0)
    reqs = [server.submit(rng.integers(1, 256, n).astype(np.int32), 3)
            for n in (5, 20, 37, 64)]
    server.drain()
    spans = {s.attrs["request"]: s for s in tracer().spans()
             if s.name == "serve.first_token"}
    passes = {s.parent_id: s.attrs for s in tracer().spans()
              if s.name == "serve.passes"}
    total_rows = total_pairs = 0
    for req in reqs:
        n = len(req.prompt)
        bucket = min(b for b in (16, 32, 64) if b >= n)
        pass_rows = routed_experts._pass_rows(bucket, K, held, 16, False)
        assert pass_rows < bucket * K
        experts = np.asarray(req.routing[0][0])             # [L, n, k]
        here = ((experts >= first) & (experts < first + held)).sum(
            axis=(1, 2))
        rows = sum(-(-int(h) // pass_rows) * pass_rows for h in here)
        attrs = passes.get(spans[req.id].span_id, {})
        assert attrs.get("moe_rows_run", 0) == rows
        assert attrs.get("moe_pairs_run", 0) == int(here.sum())
        total_rows += rows
        total_pairs += int(here.sum())
    assert total_rows > total_pairs > 0
    st = server.stats()
    assert (st["moe_rows_run"], st["moe_pairs_run"]) == (total_rows,
                                                         total_pairs)
    assert len(passes) == sum(1 for a in passes.values()
                              if a["moe_rows_run"]) <= len(reqs)
