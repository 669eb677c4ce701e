"""GLM-5.2's language model (latent attention with a compressed query over a
lightning indexer's selection, computed in the ``"full"`` layers and reused by
the ``"shared"`` ones, sigmoid routing with a shared expert, one chip's share
of the experts) through ``TransformerLM`` and ``DecodeServer`` against the
plain reference (``benchmarks/lib/reference_glm_dsa.py``), at a small size
with the published model's proportions: hidden 64, 4 heads, a compressed
query of 24, a latent of 32 with 8 RoPE dimensions, value heads (24) wider
than the no-position part of the keys (16), an indexer of 4 heads of 16 that
selects 8 positions a query (far fewer than the contexts, so the selection is
active), 32 experts of width 32 in one group, 4 a token, 8 held here, a shared
expert, a leading dense SwiGLU layer, vocabulary 256. float32 policy unless a
test says otherwise; ``docs/glm_dsa.md`` has the equations.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import reference_glm_dsa as ref  # noqa: E402
from deeplearning4j_tpu.models import dsa, routed_experts  # noqa: E402
from deeplearning4j_tpu.models.transformer import TransformerLM  # noqa: E402
from deeplearning4j_tpu.monitor import trace as program_trace  # noqa: E402
from deeplearning4j_tpu.serving import (  # noqa: E402
    DecodeServer, SlotKVCache, kv_pool_nbytes)
from deeplearning4j_tpu.serving import engine as eng  # noqa: E402
from deeplearning4j_tpu.serving.fleet import handoff  # noqa: E402

V, D, H, F, E, K, HELD, TOPK = 256, 64, 4, 32, 32, 4, 8, 8
MLA = {"q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 24, "gate": False}
DSA = {"n_heads": 4, "head_dim": 16, "topk": TOPK, "rope_dim": 8}
INDEXERS = ("full", "full", "shared", "shared", "shared")
# float32 on both sides: the program's absorbed attention over gathered rows,
# its cache and its batched experts differ from the reference's expanded keys
# under a mask and its expert loop in the order of their sums only
TOL = 1e-5


def _cfg(first=0, held=HELD, topk=TOPK):
    share = None if held is None else {"first_expert": first, "held": held}
    return {"num_attention_heads": H, "rms_norm_eps": 1e-5,
            "rope_theta": 8e6, **{k: v for k, v in MLA.items() if k != "gate"},
            "index_n_heads": 4, "index_head_dim": 16, "index_rope_dim": 8,
            "index_topk": topk, "num_experts_per_tok": K, "n_group": 1,
            "topk_group": 1, "routed_scaling_factor": 2.5, "share": share}


def _lm(policy="float32", indexers=INDEXERS, topk=TOPK, seed=3, first=0,
        held=HELD):
    n = len(indexers)
    lm = TransformerLM(
        vocab_size=V, d_model=D, num_heads=H, num_layers=n, d_ff=F,
        max_len=256, pos_encoding="rope", dtype_policy=policy,
        norm="rmsnorm", num_experts=E, experts_per_token=K,
        norm_topk_prob=True, tie_embeddings=False, seed=seed,
        rope_theta=8e6, rope_interleaved=True, norm_eps=1e-5,
        mixers=("mla",) * n, ffns=("glu",) + ("moe",) * (n - 1),
        glu_width=96, mla=MLA, indexers=indexers, dsa=dict(DSA, topk=topk),
        moe={"n_group": 1, "topk_group": 1, "scale": 2.5, "bias": True,
             "shared_width": F, "first": first, "held": held}).init()
    # ones and zeros would hide a norm that forgot its gain or its bias; a
    # sharper query makes attention depend on which keys it is given
    keys = jax.random.split(jax.random.PRNGKey(seed + 99), n)
    for blk, key in zip(lm.params["blocks"], keys):
        k = jax.random.split(key, 4)
        p = blk["mla"]
        p["kv_norm"]["g"] = 1 + 0.1 * jax.random.normal(k[0], (32,))
        p["q_norm"]["g"] = 1 + 0.1 * jax.random.normal(k[1], (24,))
        p["wq_b"] = 4.0 * p["wq_b"]
        if "indexer" in p:
            p["indexer"]["k_norm"] = {
                "g": 1 + 0.1 * jax.random.normal(k[2], (16,)),
                "b": 0.1 * jax.random.normal(k[3], (16,))}
    return lm


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, V, n).astype(np.int32)


@pytest.fixture(autouse=True)
def _highest(request):
    """float32 matmuls as written on both sides; the bf16 test runs the
    program at its own precision."""
    if "bf16" in request.node.name:
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks small enough for the tests' prompts to span several: 8
    positions a prefill block, 4 queries a gather."""
    monkeypatch.setattr(eng, "PREFILL_BLOCK", 8)
    monkeypatch.setattr(dsa, "ATTEND_BLOCK", 4)


def _served(lm, lengths, slots=3, buckets=(16, 32, 64), **kw):
    server = DecodeServer(lm, slots=slots, max_len=128, buckets=buckets,
                          **kw)
    reqs = [server.submit(_tokens(n, seed=n), k) for n, k in lengths]
    server.drain()
    return server, reqs


def _seq(req):
    return np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])[:-1]


def _decode_logits(monkeypatch):
    """The logits of every decode step, as the program computed them."""
    seen = []
    body = eng._decode_step_body

    def spy(*a, **kw):
        logits, kv = body(*a, **kw)
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return logits, kv

    monkeypatch.setattr(eng, "_decode_step_body", spy)
    return seen


# ---- (a) the forward is the reference ---------------------------------------
@pytest.mark.parametrize("t", [5, 8, 9, 40])
def test_forward_is_the_reference(t):
    """Contexts below, at, just above and well above ``index_topk``: logits
    at every position, with the selection active from position 8 on."""
    lm = _lm()
    toks = _tokens(t)
    got = lm.forward(lm.params, jnp.asarray(toks)[None])[0]
    want, _, masks, _ = ref.forward(lm.params, toks, _cfg())
    np.testing.assert_allclose(got, want, atol=TOL)
    assert [int(m.sum(-1).max()) for m in masks] == [min(t, TOPK)] * 2


@pytest.mark.parametrize("form", ["positions", "mask"])
def test_the_selection_is_exact_and_ties_go_to_the_lower_position(
        form, monkeypatch):
    """``dsa.select`` against a sort, in both its forms (a mask from the
    k-th largest score's bisection: several queries a row; that mask packed
    into ascending positions: one): the k best positions s <= t; a query
    with fewer than k behind it selects them all; equal scores (exact zeros
    where every head's relu is shut, -0.0 where its weight is negative) go to
    the lower position, as the reference's stable sort gives them."""
    rng = np.random.default_rng(0)
    iq = jnp.asarray(rng.normal(size=(2, 12, 4, 16)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(2, 12, 16)), jnp.float32)
    iw = jnp.asarray(rng.normal(size=(2, 12, 4)), jnp.float32)
    iw = iw.at[0, 10].set(0.0)              # a row of ties
    iw = iw.at[1, 11].set(-jnp.abs(iw[1, 11]))
    iq = iq.at[1, 11, :, :].set(-jnp.abs(keys[1, 3]))   # relus shut on key 3
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    if form == "positions":     # every query a row of its own
        selection = dsa.select(
            iq.reshape(24, 1, 4, 16), iw.reshape(24, 1, 4),
            jnp.repeat(keys, 12, axis=0), pos.reshape(24, 1), 5)
    else:
        selection = dsa.select(iq, iw, keys, pos, 5)
    assert isinstance(selection, tuple) == (form == "positions")
    got = np.asarray(dsa.selected_positions(selection, 5)).reshape(2, 12, 5)
    scores = np.asarray(dsa.index_scores(iq, iw, keys))
    for b in range(2):
        for t in range(12):
            want = np.argsort(-scores[b, t, :t + 1], kind="stable")[:5]
            have = [x for x in got[b, t].tolist() if x >= 0]
            assert sorted(have) == sorted(want.tolist())
            if form == "positions":
                assert have == sorted(have)         # ascending
    assert sorted(got[0, 10].tolist()) == [0, 1, 2, 3, 4]


@pytest.mark.parametrize("k", [1, 7, 64, 100])
def test_kth_largest_mask_is_a_sort(k):
    """The bisection on the scores' bits against numpy's stable sort:
    negative and positive scores, exact ties, -inf entries, rows with fewer
    than k finite scores."""
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(3, 5, 64)).astype(np.float32)
    scores[0, :, ::3] = 0.25                       # ties
    scores[1, 2, 10:] = -np.inf                    # ten finite scores
    scores[2, 1] = np.round(scores[2, 1])          # many small ties
    got = np.asarray(dsa.kth_largest_mask(jnp.asarray(scores), k))
    for row, mask in zip(scores.reshape(-1, 64), got.reshape(-1, 64)):
        order = np.argsort(-row, kind="stable")[:k]
        want = np.zeros(64, bool)
        want[order] = True
        want &= row > -np.inf
        np.testing.assert_array_equal(mask, want)


def _bits(case, t, k):
    """A row of T bits for ``test_mask_positions_is_flatnonzero``."""
    rng = np.random.default_rng(t + k)
    row = np.zeros(t, bool)
    if case == "some":                  # about k / 2 of them
        row[rng.choice(t, max(1, min(t, k) // 2), replace=False)] = True
    elif case == "more":                # more than k: the first k count
        row[rng.choice(t, min(t, 2 * k), replace=False)] = True
    elif case == "all":
        row[:] = True
    elif case == "run":                 # k in a run across a tile's edge
        row[t // 2 - 3:t // 2 - 3 + k] = True
    elif case == "spread":              # k, evenly
        row[::max(1, t // k)][:k] = True
    elif case == "ends":                # the first and the last position
        row[[0, t - 1]] = True
    return row                          # "none": no bit


@pytest.mark.parametrize("t, k", [(12, 1), (12, 5), (100, 5), (128, 64),
                                  (129, 64), (1000, 64), (4096, 2048),
                                  (32768, 2048)])
@pytest.mark.parametrize("case", ["some", "more", "all", "none", "run",
                                  "spread", "ends"])
def test_mask_positions_is_flatnonzero(case, t, k):
    """The packing against ``np.flatnonzero``: the first k set bits of a row
    in ascending order, ``valid`` false from the row's count on and every
    ``idx`` a position of the row; rows with fewer than k bits, none, all, T
    no multiple of the tile, k bits in one run and spread evenly. Two rows
    at once: the case's and its reverse."""
    rows = np.stack([_bits(case, t, k), _bits(case, t, k)[::-1]])
    idx, valid = dsa.mask_positions(jnp.asarray(rows)[:, None], k)
    assert idx.shape == valid.shape == (2, 1, k) and idx.dtype == jnp.int32
    idx, valid = np.asarray(idx)[:, 0], np.asarray(valid)[:, 0]
    assert ((idx >= 0) & (idx < t)).all()
    for row, i, v in zip(rows, idx, valid):
        want = np.flatnonzero(row)[:k]
        assert v.tolist() == [True] * len(want) + [False] * (k - len(want))
        assert i[v].tolist() == want.tolist()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _sorts(jaxpr):
    """Every ``sort`` / ``top_k`` equation of a jaxpr and of the jaxprs inside
    it: (primitive, the length of the axis it orders, its name stack)."""
    return [(eqn.primitive.name,
             eqn.invars[0].aval.shape[eqn.params.get("dimension", -1)],
             str(eqn.source_info.name_stack)) for eqn in _eqns(jaxpr)
            if "sort" in eqn.primitive.name or "top_k" in eqn.primitive.name]


def test_no_program_of_the_model_sorts_its_keys():
    """Neither the decode step nor a prefill block holds a ``sort`` or a
    ``top_k`` over the cached keys, under ``dsa.index`` or outside it (the
    record of a block's last selection): the selection is a bisection and a
    packing. What the programs do order is the router's scores, over the
    experts and their groups (at most 32 here, 48 keys a slot), under
    ``moe.route``."""
    lm = _lm()
    engine = eng.DecodeEngine(lm, 2, max_len=48, buckets=(16,))
    sample = eng._row_sampler(0.0, None)
    state = engine.cache.state
    decode = jax.make_jaxpr(lambda *a: eng._serve_decode_impl(
        lm, sample, *a))(
            lm.params, state, jnp.zeros((2,), jnp.int32),
            jnp.array([20, 30], jnp.int32),
            jax.random.split(jax.random.PRNGKey(0), 2))
    carry = {name: jnp.full(shape, fill, jnp.dtype(dt)) for name, (
        shape, dt, fill) in eng.prefill_carry_layout(lm, 16).items()}
    prefill = jax.make_jaxpr(lambda *a: eng._serve_prefill_block_impl(
        lm, sample, *a))(
            lm.params, state, carry, jnp.zeros((1, 16), jnp.int32),
            jnp.int32(9), jnp.int32(0), jax.random.PRNGKey(0), jnp.int32(0))
    for program in (decode, prefill):
        sorts = _sorts(program.jaxpr)
        assert sorts, "the router's top_k went: is this the model's program?"
        for name, axis, scope in sorts:
            assert "moe.route" in scope and axis <= E, (name, axis, scope)


def test_a_shared_layer_attends_the_preceding_full_layers_set():
    """The cross-layer value: a ``"shared"`` layer has no indexer of its own
    and what it attends is the last ``"full"`` layer's selection. Changing
    that full layer's indexer changes the shared layers' attention
    (reference and program alike), and a model whose later layers are
    ``"full"`` with their own indexers is another model."""
    lm = _lm()
    toks = _tokens(30)
    assert ["indexer" in b["mla"] for b in lm.params["blocks"]] == [
        True, True, False, False, False]
    picked = []
    real = dsa.attend_selected

    def spy(q_nope, q_rope, rows, selection, p, **kw):
        picked.append(np.sort(np.asarray(
            dsa.selected_positions(selection, TOPK)), -1))
        return real(q_nope, q_rope, rows, selection, p, **kw)

    dsa.attend_selected = spy
    try:
        got = lm.forward(lm.params, jnp.asarray(toks)[None])[0]
    finally:
        dsa.attend_selected = real
    assert len(picked) == 5
    assert (picked[0] != picked[1]).any()
    for shared in picked[2:]:
        np.testing.assert_array_equal(shared, picked[1])
    # the reference's masks name the same sets
    want, _, masks, _ = ref.forward(lm.params, toks, _cfg())
    np.testing.assert_allclose(got, want, atol=TOL)
    for layer, mask in zip(picked[:2], masks):
        for t in range(30):
            assert [x for x in layer[0, t] if x >= 0] == np.nonzero(
                np.asarray(mask[t]))[0].tolist()
    # another full layer's indexer moves the shared layers' output
    other = _lm()
    other.params = jax.tree_util.tree_map(lambda x: x, lm.params)
    other.params["blocks"][1]["mla"]["indexer"]["wq"] = -lm.params[
        "blocks"][1]["mla"]["indexer"]["wq"]
    moved = other.forward(other.params, jnp.asarray(toks)[None])[0]
    assert float(jnp.abs(moved[TOPK:] - got[TOPK:]).max()) > 1e-3
    np.testing.assert_allclose(moved[:TOPK], got[:TOPK], atol=TOL)


def test_contexts_below_index_topk_are_plain_mla():
    """With ``index_topk`` no smaller than the context every position
    attends every position before it: the same logits as a model whose
    layers have no indexer at all (``indexers`` None), and as the
    reference's dense control."""
    lm = _lm(topk=64)
    toks = _tokens(40)
    got = lm.forward(lm.params, jnp.asarray(toks)[None])[0]
    plain = _lm(indexers=(None,) * 5)
    plain.params = jax.tree_util.tree_map(lambda x: x, lm.params)
    for blk in plain.params["blocks"]:
        blk["mla"] = {k: v for k, v in blk["mla"].items() if k != "indexer"}
    dense = plain.forward(plain.params, jnp.asarray(toks)[None])[0]
    np.testing.assert_allclose(got, dense, atol=TOL)
    want = ref.forward(lm.params, toks, _cfg(topk=64), dense=True)[0]
    np.testing.assert_allclose(got, want, atol=TOL)
    # and with the selection active the dense control is another model
    sparse = _lm()
    sparse.params = lm.params
    out = sparse.forward(lm.params, jnp.asarray(toks)[None])[0]
    assert float(jnp.abs(out[TOPK:] - got[TOPK:]).max()) > 1e-3


# ---- (b) prefill, then decode through the cache -----------------------------
def test_prefill_then_decode_is_the_reference_forward(small_blocks):
    """n prompt tokens through the prefill in blocks, then k tokens a step
    at a time through the slot cache (latent rows in every layer, index keys
    in the full ones), five requests over three slots: every token is the
    reference's argmax over the whole sequence."""
    lm = _lm()
    _, reqs = _served(lm, [(5, 9), (16, 5), (37, 20), (20, 7), (9, 12)])
    for r in reqs:
        want = np.asarray(ref.forward(lm.params, _seq(r), _cfg())[0])
        n = len(r.tokens)
        assert r.tokens == np.argmax(want[-n:], -1).tolist()


def test_decode_logits_equal_the_reference(monkeypatch, small_blocks):
    """Logits, not tokens: the decode program's logits for a slot after n
    prompt tokens (two prefill blocks and a pad tail) and j steps are the
    reference's at position n + j."""
    lm = _lm()
    seen = _decode_logits(monkeypatch)
    server = DecodeServer(lm, slots=2, max_len=64, buckets=(16, 32))
    req = server.submit(_tokens(21), 7)
    server.drain()
    want = np.asarray(ref.forward(lm.params, _seq(req), _cfg())[0])[-6:]
    got = np.stack([s[req.slot] for s in seen[:6]])
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("block", [1, 2, 4, 8, 16, 32])
def test_prefill_in_blocks_is_prefill_in_one_block(block, monkeypatch):
    """The rung of 32 in 32, 16, 8, 4, 2 blocks and in 1: the same first
    token, the same latent rows and index keys for the prompt's 27
    positions, the same routing record, the same selection for its last
    position. Blocks of one position select as a decode step does
    (positions, a gather), the others under a mask."""
    lm = _lm()
    monkeypatch.setattr(dsa, "ATTEND_BLOCK", 4)

    def prefill(size):
        monkeypatch.setattr(eng, "PREFILL_BLOCK", size)
        engine = eng.DecodeEngine(lm, 2, max_len=64, buckets=(32,))
        tok, _, (routing, selection) = engine.prefill(
            _tokens(27), 1, jax.random.PRNGKey(0))
        rows = [np.asarray(a[1, :27]) for a in
                engine.cache.latent + engine.cache.index]
        return (int(tok), rows, np.asarray(routing), np.asarray(selection),
                eng.prefill_block_count(27, 32))

    tok, rows, routing, selection, n = prefill(block)
    assert n == -(-27 // block)
    tok1, rows1, routing1, selection1, n1 = prefill(32)
    assert n1 == 1 and tok == tok1
    for a, b in zip(rows, rows1):
        np.testing.assert_allclose(a, b, atol=TOL)
    experts = eng.unpack_routing(routing, HELD, K)[1]
    np.testing.assert_array_equal(
        experts[:, :27], eng.unpack_routing(routing1, HELD, K)[1][:, :27])
    np.testing.assert_array_equal(np.sort(selection), np.sort(selection1))
    assert selection.shape == (2, TOPK) and (selection >= 0).all()


def test_bf16_server_stays_within_the_benchmarks_check(small_blocks):
    """The cell's policy, bf16 compute from float32 weights, judged as the
    benchmark's check judges: the reference computes the sequence with the
    experts and, at the generated positions, the key sets the programs
    chose; tokens within ``near_tie``, routes and selections admissible. At
    8 keys a query one flipped selection in the prompt is an eighth of a
    position's attention (at 2,048, a two-thousandth), so the limits here
    are this size's, not the cell's."""
    lm = _lm("bf16")
    server = DecodeServer(lm, slots=3, max_len=128, buckets=(16, 32, 64),
                          record_routing=True)
    req = server.submit(_tokens(30), 16)
    server.drain()
    toks = np.asarray(req.tokens, np.int32)
    seq = _seq(req)
    experts = np.concatenate([r[0] for r in req.routing], axis=1)
    selected = np.concatenate(req.selection, axis=1)
    assert experts.shape == (4, len(seq), K)
    assert selected.shape == (2, len(toks), TOPK)
    logits, routes, picks = ref.forward_tail(
        lm.params, seq, _cfg(), len(toks), chosen=experts, selected=selected)
    logits = np.asarray(logits)
    gap = (logits.max(-1) - logits[np.arange(len(toks)), toks]) \
        / np.abs(logits).max(-1)
    assert gap.max() <= 0.25
    assert max(float(r[3].max()) for r in routes) <= 0.5
    for shortfall, wrong, overlap in picks:
        assert int(np.asarray(wrong).sum()) == 0
        assert float(np.asarray(shortfall).max()) <= 0.5
        assert float(np.asarray(overlap).min()) >= 0.75    # 6 of 8 keys


def test_the_reference_judges_a_handed_in_selection():
    """``forward_tail(selected=)``: the program's own float32 selections are
    the reference's (shortfall 0, nothing wrong, overlap 1); a key beyond
    the query, a key named twice and a missing key are ``wrong``; a key far
    down the ranking has a shortfall and takes an eighth off the overlap;
    the dense control gives other logits."""
    lm = _lm()
    server = DecodeServer(lm, slots=1, max_len=64, buckets=(32,),
                          record_routing=True)
    req = server.submit(_tokens(20), 6)
    server.drain()
    seq = _seq(req)
    experts = np.concatenate([r[0] for r in req.routing], axis=1)
    selected = np.concatenate(req.selection, axis=1)        # [2, 6, 8]
    logits, _, picks = ref.forward_tail(lm.params, seq, _cfg(), 6, pad_to=32,
                                        chosen=experts, selected=selected)
    own = ref.forward(lm.params, seq, _cfg())[0][-6:]
    np.testing.assert_allclose(logits, own, atol=TOL)
    for shortfall, wrong, overlap in picks:
        assert not np.asarray(wrong).any() and not np.asarray(shortfall).any()
        assert np.asarray(overlap).tolist() == [1.0] * 6
    # a tail longer than the rows handed in keeps the reference's own there
    longer = ref.forward_tail(lm.params, seq, _cfg(), 9, pad_to=32,
                              chosen=experts, selected=selected)[0]
    np.testing.assert_allclose(
        longer, ref.forward(lm.params, seq, _cfg())[0][-9:], atol=TOL)
    bad = selected.copy()
    bad[0, 0, 0] = 24                       # beyond the query (position 19)
    bad[0, 1, 0] = bad[0, 1, 1]             # named twice
    bad[1, 2, 3] = -1                       # one key short
    weakest = int(np.setdiff1d(np.arange(20), selected[1, 4])[0])
    bad[1, 4, 0] = weakest                  # a key the reference ranks lower
    _, _, picks = ref.forward_tail(lm.params, seq, _cfg(), 6, pad_to=32,
                                   chosen=experts, selected=bad)
    assert np.asarray(picks[0][1]).tolist() == [1, 1, 0, 0, 0, 0]
    assert np.asarray(picks[1][1]).tolist() == [0, 0, 1, 0, 0, 0]
    assert float(picks[1][0][4]) > 0
    # the overlap, with layer 0's selections left alone (spoilt, they move
    # the states that layer 1's own selection is computed from)
    bad[0] = selected[0]
    _, _, picks = ref.forward_tail(lm.params, seq, _cfg(), 6, pad_to=32,
                                   chosen=experts, selected=bad)
    assert np.asarray(picks[0][2]).tolist() == [1.0] * 6
    assert np.asarray(picks[1][2]).tolist() == [1, 1, 7 / 8, 1, 7 / 8, 1]
    dense = ref.forward_tail(lm.params, seq, _cfg(), 6, pad_to=32,
                             chosen=experts, dense=True)[0]
    assert float(jnp.abs(dense - own).max()) > 1e-3


# ---- (c) the share -----------------------------------------------------------
@pytest.mark.parametrize("rows", [24, 40])
def test_the_shares_add_up_to_the_uncut_layer(rows, monkeypatch):
    """Four chips hold 8 of the 32 experts each (one group, ``n_group`` 1).
    Their routed parts, and the shared expert counted once, are the uncut
    reference layer; in the dense form (24 rows) and in the sorted one (40
    rows, past a lowered threshold)."""
    monkeypatch.setattr(routed_experts, "DENSE_MAX_TOKENS", 32)
    p = _lm(held=None).params["blocks"][2]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, D))
    kw = dict(experts_per_token=K, norm_topk_prob=True, groups=(1, 1, 2.5))
    total = jnp.zeros_like(x)
    pairs = 0
    for chip in range(E // HELD):
        mine = {k: (v[chip * HELD:(chip + 1) * HELD]
                    if k.startswith("w_") else v) for k, v in p.items()}
        with_shared, info = routed_experts.routed_ffn(
            x, mine, first=chip * HELD, **kw)
        del mine["shared"]
        routed, _ = routed_experts.routed_ffn(x, mine, first=chip * HELD,
                                              **kw)
        total = total + routed
        shared = with_shared - routed         # every chip computes it alike
        pairs += int(info["load"].sum())
        want, _ = ref.expert_layer(x, {**mine, "shared": p["shared"]},
                                   _cfg(first=chip * HELD))
        np.testing.assert_allclose(with_shared, want, atol=TOL)
    assert pairs == rows * K                  # every pair landed on one chip
    uncut, _ = ref.expert_layer(x, p, _cfg(held=None))
    np.testing.assert_allclose(total + shared, uncut, atol=TOL)


# ---- (d) slots ---------------------------------------------------------------
def test_a_reused_slot_and_a_pad_tail_change_nothing(small_blocks):
    """One slot, three requests one after another, the second shorter than
    the first (it inherits rows beyond its own, a pad tail inside its last
    block and stale index keys): each gives the tokens it gives alone in a
    fresh server."""
    lm = _lm()
    lengths = [(40, 12), (11, 9), (21, 15)]
    _, reqs = _served(lm, lengths, slots=1)
    assert [r.slot for r in reqs] == [0, 0, 0]
    for (n, k), r in zip(lengths, reqs):
        _, (alone,) = _served(lm, [(n, k)], slots=1)
        assert r.tokens == alone.tokens


def _alone(lm, n, k, seed=None):
    _, (req,) = _served(lm, [(n, k)], slots=1)
    return req.tokens


def test_prefill_blocks_take_turns_with_decode_steps(small_blocks):
    """One prefill block a scheduler step: while a 37-token prompt (five
    blocks) is on its way into its slot, the slot that decodes emits a token
    every step, and a 9-token prompt (two blocks) that arrived behind it
    takes its turns and has its first token first. Every request gives the
    tokens it gives alone."""
    lm = _lm()
    server = DecodeServer(lm, slots=3, max_len=128, buckets=(16, 32, 64))
    first = server.submit(_tokens(12, seed=12), 30)
    while not first.tokens:
        server.step()
    spans = []
    program_trace.add_sink(spans.append)
    try:
        long = server.submit(_tokens(37, seed=37), 6)
        short = server.submit(_tokens(9, seed=9), 6)
        emitted = []
        while not long.tokens:
            server.step()
            emitted.append(len(first.tokens))
    finally:
        program_trace.remove_sink(spans.append)
    blocks = [(s["name"], s["attrs"]["request"]) for s in spans
              if s["name"] in ("serve.prefill", "serve.prefill_block")]
    assert blocks == [
        ("serve.prefill_block", long.id), ("serve.prefill_block", short.id),
        ("serve.prefill_block", long.id), ("serve.prefill", short.id),
        ("serve.prefill_block", long.id), ("serve.prefill_block", long.id),
        ("serve.prefill", long.id)]
    assert [s["attrs"]["blocks"] for s in spans
            if s["name"] == "serve.prefill"] == [2, 5]
    # a step a block, and the decoding slot's token read in each
    assert len(emitted) == 7 and np.all(np.diff(emitted) == 1)
    assert short.first_token_s < long.first_token_s
    server.drain()
    assert server.stats()["prefill_blocks"] == 2 + 2 + 5
    for req, (n, k) in ((first, (12, 30)), (long, (37, 6)), (short, (9, 6))):
        assert req.tokens == _alone(lm, n, k)


def test_a_slot_frozen_inside_the_next_prompt_spoils_no_row(small_blocks):
    """A slot whose last request stopped at cursor 19 takes a 40-token
    prompt while the other slot decodes: the decode steps between its
    blocks write the frozen slot's row, which must not be row 19 of the
    new prompt (``engine.prefill_blocks`` moves the frozen cursor to the
    prompt's length first)."""
    lm = _lm()
    server = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 32, 64))
    before = server.submit(_tokens(11, seed=11), 9)
    other = server.submit(_tokens(6, seed=6), 40)
    while before.state != "finished":
        server.step()
    after = server.submit(_tokens(40, seed=40), 5)
    server.drain()
    assert after.slot == before.slot and len(other.tokens) == 40
    assert after.tokens == _alone(lm, 40, 5)
    assert other.tokens == _alone(lm, 6, 40)


def test_a_request_that_leaves_between_blocks_frees_its_slot(small_blocks):
    """Canceled after its first block: the slot is free at the next step,
    the rung's carry is back on its free list, and the next request in the
    slot gives the tokens it gives alone."""
    lm = _lm()
    server = DecodeServer(lm, slots=1, max_len=128, buckets=(16, 32, 64))
    gone = server.submit(_tokens(37, seed=37), 6)
    server.step()
    assert server.free_slot_count() == 0 and not gone.tokens
    gone.canceled = True
    server.step()
    assert server.free_slot_count() == 1 and not server._prefilling
    assert [len(v) for v in server.engine._prefill_carry.values()] == [1]
    req = server.submit(_tokens(21, seed=21), 7)
    server.drain()
    assert gone.state == "canceled" and not gone.tokens
    assert req.tokens == _alone(lm, 21, 7)


def test_a_slot_that_owes_nothing_keeps_its_rows():
    """A finished request's slot rides along in the next steps: its latent
    rows and index keys below its cursor stay as its last step left them."""
    lm = _lm()
    server = DecodeServer(lm, slots=2, max_len=64, buckets=(16,))
    short = server.submit(_tokens(5), 2)
    server.submit(_tokens(6, seed=1), 12)
    while short.state != "finished":
        server.step()
    server.flush()
    cache = server.engine.cache
    before = [np.asarray(a[short.slot, :6]) for a in
              cache.latent + cache.index]
    server.drain()
    cache = server.engine.cache
    after = [np.asarray(a[short.slot, :6]) for a in
             cache.latent + cache.index]
    assert len(before) == 5 + 2
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert all(a.any() for a in before)


def test_pool_bytes_count_the_index_keys():
    lm = _lm()
    slots, t = 3, 40
    cache = SlotKVCache(lm, slots, t, "bfloat16")
    want = {"kv": 0, "latent": 5 * slots * t * 128 * 2,  # 40 numbers, 128 lanes
            "index": 2 * slots * t * 16 * 2,            # the two full layers
            "recurrent": 0, "conv": 0}
    assert cache.nbytes_by_kind == want
    assert cache.nbytes == sum(want.values()) \
        == kv_pool_nbytes(lm, slots, t, "bfloat16")
    assert [a.shape for a in cache.index] == [(slots, t, 16)] * 2


# ---- (d2) a decode step reads the slots that owe a token ---------------------
LIVE = {"none": None, "one": [False, False, True, False],
        "some": [True, False, True, False], "all": [True] * 4,
        "nobody": [False] * 4}


@pytest.mark.parametrize("xp", ["numpy", "jax"])
@pytest.mark.parametrize("case", list(LIVE))
def test_live_slots_is_the_live_slots_first_and_their_count(case, xp):
    """The work list of the one-query form's reads: the slots that owe a
    token in ascending order, then no slot (``slots``); their count; all of
    them for ``live=None``. numpy on the host (the server's
    ``rows_gathered``), jax values in a program, the same arithmetic."""
    live = LIVE[case]
    want = list(range(4)) if live is None else np.flatnonzero(live).tolist()
    if live is not None:
        live = (np if xp == "numpy" else jnp).asarray(live)
    order, count = dsa.live_slots(live, 4)
    if xp == "jax" and live is not None:
        assert isinstance(order, jax.Array)
        order, count = jax.jit(lambda m: dsa.live_slots(m, 4))(live)
    assert int(count) == len(want)
    assert np.asarray(order).tolist() == want + [4] * (4 - len(want))
    assert np.asarray(order).dtype == np.int32


def _pool_of_noise(lm, slots, t_max, seed=0):
    """A pool whose every row holds something: what a dead slot leaves
    behind must not matter, and a row that moved shows."""
    state = SlotKVCache(lm, slots, t_max, "float32").state
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    return {name: [jax.random.normal(next(keys), a.shape, a.dtype)
                   for a in arrays] for name, arrays in state.items()}


def _decode_step(lm, kv, tok, positions, live):
    selections = []
    logits, new = eng._decode_step_body(
        lm, lm.params, kv, tok, positions, live=live, selections=selections)
    k = min(TOPK, kv["latent"][0].shape[1])
    return logits, new, eng._stack_selection(selections, k)[:, :, 0]


@pytest.mark.parametrize("context", ["below_topk", "above_topk"])
@pytest.mark.parametrize("case", ["none", "one", "some", "all"])
def test_a_decode_step_over_the_live_slots_is_the_step_over_all(case,
                                                                context):
    """``_decode_step_body`` with a live mask against the same step with
    none (every slot scored and gathered): the live slots select the same
    positions, exactly, and their logits are the same; the others' logits
    are finite and their record is empty; both forms write the cursor's
    row in every slot and layer, the same for a live slot, and no other row
    of the pool moves. Cursors below ``index_topk`` (a query selects all it has) and
    above it."""
    lm = _lm()
    slots, t_max = 4, 48
    kv = _pool_of_noise(lm, slots, t_max)
    positions = jnp.asarray([3, 6, 0, 5] if context == "below_topk"
                            else [20, 37, 9, 47], jnp.int32)
    tok = jnp.asarray(_tokens(slots, seed=5))
    live = None if LIVE[case] is None else jnp.asarray(LIVE[case])
    step = jax.jit(lambda kv, live: _decode_step(lm, kv, tok, positions,
                                                 live))
    want_logits, want_kv, want_sel = step(kv, None)
    logits, new, sel = step(kv, live)
    owing = np.ones(slots, bool) if live is None else np.asarray(live)
    np.testing.assert_array_equal(np.asarray(sel)[:, owing],
                                  np.asarray(want_sel)[:, owing])
    np.testing.assert_allclose(np.asarray(logits)[owing],
                               np.asarray(want_logits)[owing], atol=TOL)
    assert np.isfinite(np.asarray(logits)).all()
    assert (np.asarray(sel)[:, ~owing] == -1).all()
    # a live slot's record names min(cursor + 1, topk) positions behind it
    for s in np.flatnonzero(owing):
        named = np.asarray(sel)[:, s]
        assert ((named >= 0).sum(-1) == min(int(positions[s]) + 1,
                                            TOPK)).all()
        assert (named <= int(positions[s])).all()
    at = (np.arange(slots), np.asarray(positions))
    for name in ("latent", "index"):
        for before, a, b in zip(kv[name], new[name], want_kv[name]):
            before, a, b = (np.array(x) for x in (before, a, b))
            # (a slot that owes nothing writes what nobody reads)
            np.testing.assert_allclose(a[at][owing], b[at][owing], atol=TOL)
            assert (a[at] != before[at]).any(-1).all()    # the cursor's row
            a[at] = before[at]
            np.testing.assert_array_equal(a, before)      # and no other


def test_the_decode_program_gathers_a_slot_at_a_time():
    """No gather of the decode program fetches latent rows for every slot
    at once: the selected rows come ``index_topk`` a trip of a loop over the
    live slots, one such gather a layer, and the program holds one ``while``
    for each of them and for each layer that scores."""
    lm = _lm()
    slots = 3
    engine = eng.DecodeEngine(lm, slots, max_len=48, buckets=(16,))
    sample = eng._row_sampler(0.0, None)
    program = jax.make_jaxpr(lambda *a: eng._serve_decode_loop_impl(
        lm, sample, *a))(lm.params, engine.cache.state, engine.cache.loop)
    width = engine.cache.latent[0].shape[-1]
    rows = [eqn.outvars[0].aval.shape for eqn in _eqns(program.jaxpr)
            if eqn.primitive.name == "gather"
            and eqn.outvars[0].aval.shape[-1] == width]
    assert rows == [(1, TOPK, width)] * 5
    text = str(program)
    assert text.count("while[") == 5 + 2


def test_rows_gathered_counts_the_work_list(small_blocks):
    """``serve.decode`` carries ``rows_gathered`` = the live slots x
    ``index_topk`` x the five 'mla' layers, whatever their cursors (a slot
    below ``index_topk`` attends fewer rows than its trip gathers);
    ``stats()`` carries the total."""
    lm = _lm()
    spans = []
    program_trace.add_sink(spans.append)
    try:
        server, reqs = _served(lm, [(4, 6), (21, 3), (13, 9)], slots=3)
    finally:
        program_trace.remove_sink(spans.append)
    decode = [s["attrs"] for s in spans if s["name"] == "serve.decode"
              and s["attrs"]["live"]]
    assert {d["live"] for d in decode} >= {1, 2}
    for d in decode:
        assert d["rows_gathered"] == d["live"] * TOPK * 5
        assert d["keys_attended"] <= d["rows_gathered"]
    # the 4-token prompt's first steps have fewer than 8 rows behind them
    assert any(d["keys_attended"] < d["rows_gathered"] for d in decode)
    st = server.stats()
    assert st["rows_gathered"] == sum(d["rows_gathered"] for d in decode)
    assert st["rows_gathered"] == server.slot_dispatches * TOPK * 5


# ---- (e) spans and counters --------------------------------------------------
def test_spans_and_counters(small_blocks):
    """``serve.prefill`` carries ``blocks``; ``serve.decode`` carries
    ``keys_cached`` and ``keys_attended`` (summed over live slots and the
    five layers); ``stats()`` their totals, their ratio and ``index`` among
    the state bytes."""
    lm = _lm()
    spans = []
    program_trace.add_sink(spans.append)
    try:
        server, (req,) = _served(lm, [(21, 6)], slots=2)
    finally:
        program_trace.remove_sink(spans.append)
    prefill = [s for s in spans if s["name"] == "serve.prefill"]
    assert [s["attrs"]["blocks"] for s in prefill] == [3]    # 21 in 8s
    decode = [s["attrs"] for s in spans if s["name"] == "serve.decode"
              and "keys_cached" in s["attrs"]]
    # the step that consumes the token at cursor c has c + 1 rows behind it
    assert [d["keys_cached"] for d in decode] == [
        5 * (21 + j + 1) for j in range(5)]
    assert all(d["keys_attended"] == 5 * TOPK for d in decode)
    st = server.stats()
    assert st["keys_cached"] == sum(d["keys_cached"] for d in decode)
    assert st["keys_attended"] == 5 * 5 * TOPK
    assert st["keys_attended_share"] == round(
        st["keys_attended"] / st["keys_cached"], 4)
    assert st["prefill_blocks"] == 3
    assert st["state_bytes"]["index"] == server.engine.cache.nbytes_by_kind[
        "index"] > 0
    assert st["kv_pool_bytes"] == sum(st["state_bytes"].values())


def test_the_scopes_name_the_parts():
    """``dsa.index``, ``mla.proj`` and ``mla.attend`` reach the decode
    program's and the prefill block's HLO."""
    lm = _lm()
    engine = eng.DecodeEngine(lm, 2, max_len=32, buckets=(16,))
    sample = eng._row_sampler(0.0, None)
    decode = jax.jit(lambda p, kv, loop: eng._serve_decode_loop_impl(
        lm, sample, p, kv, loop)).lower(
            lm.params, engine.cache.state, engine.cache.loop).as_text(
                debug_info=True)
    carry = {name: jnp.full(shape, fill, jnp.dtype(dt)) for name, (
        shape, dt, fill) in eng.prefill_carry_layout(lm, 16).items()}
    prefill = jax.jit(lambda *a: eng._serve_prefill_block_impl(
        lm, sample, *a)).lower(
            lm.params, engine.cache.state, carry, jnp.zeros((1, 16), jnp.int32),
            jnp.int32(9), jnp.int32(0), jax.random.PRNGKey(0),
            jnp.int32(0)).as_text(debug_info=True)
    for text in (decode, prefill):
        for scope in ("dsa.index", "mla.proj", "mla.attend", "moe.route"):
            assert scope in text


# ---- (f) the description -----------------------------------------------------
def test_get_config_rebuilds_the_model():
    lm = _lm()
    again = TransformerLM(**lm.get_config()).init()
    assert again.indexers == INDEXERS and again.dsa == DSA
    assert jax.tree_util.tree_structure(again.params) \
        == jax.tree_util.tree_structure(lm.params)
    specs = lm.param_specs(model_axis_size=1)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, lm.params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda x: 0, specs, is_leaf=lambda x: isinstance(x, tuple)))


@pytest.mark.parametrize("bad", ["no_sizes", "kind", "not_mla", "no_full",
                                 "no_query_rank", "length"])
def test_a_description_that_cannot_be_built_is_refused(bad):
    kw = dict(vocab_size=V, d_model=D, num_heads=H, num_layers=2, d_ff=F,
              pos_encoding="rope", norm="rmsnorm", mixers=("mla", "mla"),
              ffns=("glu", "glu"), glu_width=96, mla=MLA, dsa=DSA,
              indexers=("full", "shared"))
    kw.update({"no_sizes": {"dsa": None},
               "kind": {"indexers": ("full", "half")},
               "not_mla": {"mixers": ("mla", "attn")},
               "no_full": {"indexers": ("shared", "full")},
               "no_query_rank": {"mla": {k: v for k, v in MLA.items()
                                         if k != "q_lora_rank"}},
               "length": {"indexers": ("full",)}}[bad])
    with pytest.raises(ValueError, match="indexer"):
        TransformerLM(**kw)


@pytest.mark.parametrize("what", ["generate", "beam", "mesh", "handoff",
                                  "scan_layers"])
def test_paths_without_the_new_state_refuse_the_model(what):
    """Every serving path that carries K/V only names what it lacks
    instead of decoding garbage."""
    lm = _lm()
    prompt = _tokens(5)[None]
    if what == "generate":
        with pytest.raises(NotImplementedError, match="indexer's keys"):
            lm.generate(prompt, 3)
    elif what == "beam":
        with pytest.raises(NotImplementedError, match="latent"):
            lm.generate_beam(prompt, 3, beam_size=2)
    elif what == "mesh":
        from deeplearning4j_tpu.parallel.sharding_registry import (
            ShardingRegistry)
        from deeplearning4j_tpu.parallel import build_mesh
        from deeplearning4j_tpu.parallel.mesh import MeshSpec

        mesh = build_mesh(MeshSpec(model=1), devices=jax.devices()[:1])
        with pytest.raises(ValueError, match="one chip"):
            SlotKVCache(lm, 1, 32, "bfloat16",
                        registry=ShardingRegistry.for_transformer(lm, mesh))
    elif what == "handoff":
        server = DecodeServer(lm, slots=1, max_len=32, buckets=(16,))
        with pytest.raises(ValueError, match="hand-off"):
            handoff.export_slot(server.engine, 0)
    else:
        cfg = dict(lm.get_config(), scan_layers=True)
        with pytest.raises(ValueError, match="scan_layers"):
            TransformerLM(**cfg).init().forward(lm.params,
                                                jnp.asarray(prompt))
