"""Brumby-14B-Base's block (every layer power retention, degree 2, gated;
then a SwiGLU) through ``TransformerLM`` and ``DecodeServer`` against the
plain reference (``benchmarks/lib/reference_brumby.py``: the quadratic form,
no state and no ``phi``), at a small size with the published model's
proportions: hidden 64, 4 query heads over 2 key/value heads of 16, a SwiGLU
of 96, vocabulary 256, two layers. float32 policy throughout.

Tolerances. The program and the reference compute the same sums in another
order (a recurrence on a state against a masked matrix of powers), in
float32 at ``highest``: they agree to a few float32 roundings of a sum of a
hundred terms, 1e-5 of the largest output (``TOL``; measured 2e-7 .. 2e-6).
A state rounded to bf16 carries 2^-9 = 2e-3 a term and reads 1e-3 .. 1e-2 by
the same comparison: ``test_a_bf16_state_fails_the_same_comparison`` holds
that every one of these comparisons would refuse it.
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

from benchmarks.lib import reference_brumby as ref  # noqa: E402
from deeplearning4j_tpu.models import ret  # noqa: E402
from deeplearning4j_tpu.models.transformer import TransformerLM  # noqa: E402
from deeplearning4j_tpu.monitor import trace as program_trace  # noqa: E402
from deeplearning4j_tpu.pallas.retention_step import (  # noqa: E402
    retention_step)
from deeplearning4j_tpu.serving import (  # noqa: E402
    DecodeServer, SlotKVCache, kv_pool_nbytes, max_slots_in_budget)
from deeplearning4j_tpu.serving import engine as eng  # noqa: E402
from deeplearning4j_tpu.serving.fleet import handoff  # noqa: E402
from deeplearning4j_tpu.serving.kv_cache import pool_layout  # noqa: E402

V, D, H, HKV, DH, G = 256, 64, 4, 2, 16, 96
TOL = 1e-5
CFG = {"rms_norm_eps": 1e-6, "rope_theta": 1e6, "num_attention_heads": H,
       "num_key_value_heads": HKV, "head_dim": DH, "power": 2,
       "ret_eps": ret.EPS}


def _lm(seed=3, layers=2, **over):
    kw = dict(vocab_size=V, d_model=D, num_heads=H, num_kv_heads=HKV,
              num_layers=layers, max_len=256, pos_encoding="rope",
              dtype_policy="float32", norm="rmsnorm", norm_eps=1e-6,
              rope_theta=1e6, tie_embeddings=False, seed=seed,
              mixers=("ret",) * layers, ffns=("glu",) * layers, glu_width=G,
              ret={"power": 2})
    kw.update(over)
    lm = TransformerLM(**kw).init()
    # unit gains would hide a norm that forgot its gain
    keys = jax.random.split(jax.random.PRNGKey(seed + 99), layers)
    for blk, key in zip(lm.params["blocks"], keys):
        k = jax.random.split(key, 2)
        for name, kk in zip(("q_norm", "k_norm"), k if "ret" in blk else ()):
            blk["ret"][name]["g"] = 1.0 + 0.2 * jax.random.normal(kk, (DH,))
    return lm


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, V, n).astype(np.int32)


def _rows(t, seed=0, b=1):
    """q [b, t, H, d], k, v [b, t, Hkv, d] (q and k at the mixer's scale),
    log-gates [b, t, Hkv] between a few and a few hundred tokens of
    memory."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, t, H, DH)) * DH ** -0.25
    k = jax.random.normal(ks[1], (b, t, HKV, DH)) * DH ** -0.25
    v = jax.random.normal(ks[2], (b, t, HKV, DH))
    lg = -jnp.exp(jax.random.uniform(ks[3], (b, t, HKV), minval=-6.0,
                                     maxval=-1.2))
    return q, k, v, lg


def _reference_rows(q, k, v, lg):
    """The quadratic form on one row of ``_rows`` (the reference scales the
    score itself, so it gets q and k without the mixer's d^-1/4)."""
    return ref.retention(q[0] * DH ** 0.25, k[0] * DH ** 0.25, v[0], lg[0],
                         CFG)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _bf16(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), tree)


def _stepped(q, k, v, lg, kernel, rounded=False):
    """The step form carried over the sequence from an empty state."""
    state = ret._zero_state(1, HKV, DH)
    out = []
    for i in range(q.shape[1]):
        if kernel:
            o, s, z = retention_step(q[:, i], k[:, i], v[:, i], lg[:, i],
                                     *state, eps=ret.EPS, interpret=True)
            state = (s, z)
        else:
            o, state = ret.ret_step(q[:, i], k[:, i], v[:, i], lg[:, i],
                                    state)
        if rounded:
            state = _bf16(state)
        out.append(o[0])
    return jnp.stack(out)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ---- (a) the layout ----------------------------------------------------------
@pytest.mark.parametrize("d", [8, 16, 32, 128])
def test_phi_is_the_symmetric_square_in_the_layout_stored(d):
    ks = jax.random.split(jax.random.PRNGKey(d), 2)
    a, b = (jax.random.normal(k, (7, d)) for k in ks)
    blocks = d // ret.TILE
    assert ret.phi(a).shape == (7, ret.state_rows(d))
    assert ret.state_rows(d) == 64 * blocks * (blocks + 1) // 2
    np.testing.assert_allclose(jnp.sum(ret.phi(a) * ret.phi(b), -1),
                               jnp.sum(a * b, -1) ** 2, rtol=2e-5, atol=1e-5)


def test_the_state_of_a_head_of_128_has_8704_rows():
    assert ret.state_rows(128) == 8704      # 8,256 + 16 x 28 lower halves
    with pytest.raises(ValueError, match="multiple of 8"):
        ret.phi(jnp.ones((3, 12)))


# ---- (b) the three forms against the quadratic form --------------------------
@pytest.mark.parametrize("t,chunk", [(9, 256), (40, 8), (70, 16), (33, 8)])
def test_the_chunked_form_is_the_quadratic_form(t, chunk, monkeypatch):
    monkeypatch.setattr(ret, "CHUNK", chunk)
    q, k, v, lg = _rows(t, seed=t)
    got, _ = ret.ret_scan(q, k, v, lg, ret._zero_state(1, HKV, DH))
    assert _rel(got[0], _reference_rows(q, k, v, lg)) <= TOL


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_the_step_form_carried_over_a_sequence_is_the_quadratic_form(kernel):
    q, k, v, lg = _rows(24, seed=5)
    assert _rel(_stepped(q, k, v, lg, kernel),
                _reference_rows(q, k, v, lg)) <= TOL


def test_the_chunked_form_hands_the_step_form_its_state(monkeypatch):
    """A prompt through the chunks, then positions one at a time through the
    kernel: the reference's rows over the whole sequence."""
    monkeypatch.setattr(ret, "CHUNK", 8)
    q, k, v, lg = _rows(30, seed=9)
    head, state = ret.ret_scan(q[:, :21], k[:, :21], v[:, :21], lg[:, :21],
                               ret._zero_state(1, HKV, DH))
    tail = []
    for i in range(21, 30):
        o, *state = retention_step(q[:, i], k[:, i], v[:, i], lg[:, i],
                                   *state, eps=ret.EPS, interpret=True)
        tail.append(o)
    got = jnp.concatenate([head[0], jnp.concatenate(tail)])
    assert _rel(got, _reference_rows(q, k, v, lg)) <= TOL


@pytest.mark.parametrize("form", ["scan", "step", "kernel"])
def test_a_bf16_state_fails_the_same_comparison(form, monkeypatch):
    """The tolerance sees a state rounded to bf16 between chunks or steps:
    every comparison above would refuse it by a factor of ten or more."""
    q, k, v, lg = _rows(40, seed=2)
    want = _reference_rows(q, k, v, lg)
    if form == "scan":
        state, out = ret._zero_state(1, HKV, DH), []
        for lo in range(0, 40, 8):
            rows = (a[:, lo:lo + 8] for a in (q, k, v, lg))
            o, state = ret.ret_scan(*rows, state)
            state = _bf16(state)
            out.append(o[0])
        got = jnp.concatenate(out)
    else:
        got = _stepped(q, k, v, lg, form == "kernel", rounded=True)
    assert _rel(got, want) > 10 * TOL


@pytest.mark.parametrize("live", ["none", "some", "all"])
def test_the_kernel_is_the_step_form_over_the_live_rows(live):
    """Every row against ``ret_step``; a row that is not live keeps its
    state's bits and gives zeros."""
    b = 5
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    q, k, v, lg = (a[:, 0] for a in _rows(1, seed=7, b=b))
    s = jax.random.normal(ks[0], (b, HKV, ret.state_rows(DH), DH))
    z = jnp.abs(jax.random.normal(ks[1], (b, HKV, DH, DH))) + 1.0
    mask = {"none": np.zeros(b, bool), "all": np.ones(b, bool),
            "some": np.array([True, False, False, True, False])}[live]
    want_o, (want_s, want_z) = ret.ret_step(q, k, v, lg, (s, z))
    o, s1, z1 = retention_step(q, k, v, lg, s, z, jnp.asarray(mask),
                               eps=ret.EPS, interpret=True)
    for got, want, old in ((s1, want_s, s), (z1, want_z, z)):
        np.testing.assert_array_equal(np.asarray(got)[~mask],
                                      np.asarray(old)[~mask])
        np.testing.assert_allclose(np.asarray(got)[mask],
                                   np.asarray(want)[mask], atol=1e-5)
    assert not np.asarray(o)[~mask].any()
    if mask.any():
        assert _rel(o[mask], want_o[mask]) <= TOL


def test_a_row_that_holds_no_token_moves_no_state():
    """The mixer's rule for a pad tail: the state is as of the last live
    position, bit for bit what the unpadded rows leave."""
    lm = _lm()
    blk = lm.params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, D))
    live = (jnp.arange(16) < 11)[None]
    _, s, z = lm._block(blk, x, live=live)
    _, s0, z0 = lm._block(blk, x[:, :11])
    np.testing.assert_allclose(s, s0, atol=1e-6)
    np.testing.assert_allclose(z, z0, atol=1e-6)
    assert float(jnp.abs(s).max()) > 0


# ---- (c) the model ------------------------------------------------------------
@pytest.mark.parametrize("t", [9, 70, 130])
def test_forward_logits_are_the_references(t, monkeypatch):
    monkeypatch.setattr(ret, "CHUNK", 16)
    lm = _lm()
    toks = _tokens(t, seed=t)
    got = lm.forward(lm.params, jnp.asarray(toks)[None])[0]
    want = ref.forward_tail(lm.params, toks, CFG, t)
    assert _rel(got, want) <= TOL


def test_a_long_prompt_runs_block_after_block(monkeypatch):
    """Past twice ``SEQ_BLOCK`` the mixer takes a block at a time from the
    state the one before left, each at its own positions."""
    lm = _lm()
    toks = jnp.asarray(_tokens(48, seed=1))[None]
    whole = lm.forward(lm.params, toks)
    monkeypatch.setattr(ret, "SEQ_BLOCK", 16)
    monkeypatch.setattr(ret, "CHUNK", 8)
    cut = lm.forward(lm.params, toks)
    assert _rel(cut, whole) <= TOL


@pytest.mark.parametrize("control", ["no_decay", "p4", "softmax"])
def test_the_references_controls_change_its_logits(control):
    lm = _lm()
    toks = _tokens(40)
    plain = ref.forward_tail(lm.params, toks, CFG, 40)
    other = ref.forward_tail(lm.params, toks, {**CFG, "control": control},
                             40)
    assert _rel(other, plain) > 1e-3


def test_the_loss_and_its_gradient_are_the_references():
    lm = _lm()
    toks = _tokens(33, seed=6)
    loss, grads = jax.value_and_grad(lm.loss)(lm.params,
                                              jnp.asarray(toks)[None])
    want, want_grads = jax.value_and_grad(ref.loss)(lm.params,
                                                    jnp.asarray(toks), CFG)
    assert abs(float(loss) - float(want)) <= 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-5 + 1e-4 * float(
            jnp.max(jnp.abs(w))), jax.tree_util.keystr(path)


def test_a_train_step_lowers_the_loss():
    lm = _lm()
    toks = np.tile(_tokens(24, seed=8), (2, 1))
    first = lm.fit_batch(toks)
    for _ in range(5):
        last = lm.fit_batch(toks)
    assert last < first


def test_scan_layers_takes_the_model():
    lm = _lm()
    toks = jnp.asarray(_tokens(20))[None]
    stacked = TransformerLM(**dict(lm.get_config(), scan_layers=True))
    assert _rel(stacked.forward(lm.params, toks),
                lm.forward(lm.params, toks)) <= TOL


def test_get_config_rebuilds_the_model():
    lm = _lm()
    again = TransformerLM(**lm.get_config())
    assert again.ret == {"power": 2} and again.mixers == ("ret", "ret")
    shapes = jax.eval_shape(lambda: again.init().params)
    assert jax.tree_util.tree_map(lambda a: a.shape, shapes) == (
        jax.tree_util.tree_map(lambda a: a.shape, lm.params))
    specs = lm.param_specs(model_axis_size=1)
    assert set(specs["blocks"][0]["ret"]) == set(lm.params["blocks"][0]["ret"])


# ---- (d) generate() and the plain decode loop --------------------------------
@pytest.mark.parametrize("n,new", [(12, 8), (5, 14)])
def test_generate_carries_the_state(n, new):
    lm = _lm()
    prompt = _tokens(n, seed=n)
    out = np.asarray(lm.generate(prompt[None], new))[0]
    logits = np.asarray(ref.forward_tail(lm.params, out[:-1], CFG, new))
    np.testing.assert_array_equal(out[n:], logits.argmax(-1))


def test_beam_search_reorders_the_state_with_its_beams():
    lm = _lm()
    prompt = _tokens(9, seed=2)[None]
    seqs, scores = lm.generate_beam(prompt, 6, beam_size=3)
    assert seqs.shape == (1, 3, 15)
    # a beam's score is the reference's log-probability of its tokens
    best = np.asarray(seqs)[0, 0]
    logp = jax.nn.log_softmax(ref.forward_tail(lm.params, best[:-1], CFG, 6))
    want = float(jnp.sum(logp[np.arange(6), best[9:]]))
    assert abs(float(scores[0, 0]) - want) <= 1e-4


# ---- (e) the slot cache and the server ---------------------------------------
def _served(lm, lengths, slots=3, **server_kw):
    server = DecodeServer(lm, slots=slots, max_len=128, buckets=(16, 32, 64),
                          **server_kw)
    reqs = [server.submit(_tokens(n, seed=n), k) for n, k in lengths]
    server.drain()
    return server, reqs


def _judge(lm, reqs, tol):
    for r in reqs:
        toks = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([r.prompt, toks])[:-1]
        logits = np.asarray(ref.forward_tail(lm.params, seq, CFG, len(toks)))
        gap = (logits.max(-1) - logits[np.arange(len(toks)), toks]) \
            / np.abs(logits).max(-1)
        assert gap.max() <= tol, (len(r.prompt), gap.max())


def test_prefill_then_decode_is_the_reference_forward():
    """Ragged prompts through the bucketed prefill, then tokens one step at
    a time through the slot cache, five requests over three slots (so two
    slots are taken again after a retire, and most steps hold a slot that
    owes nothing): every token is the reference's argmax over the whole
    sequence."""
    lm = _lm()
    _, reqs = _served(lm, [(5, 9), (16, 5), (37, 20), (20, 7), (9, 12)])
    _judge(lm, reqs, 1e-5)


@pytest.mark.parametrize("n", [11, 16, 3])
def test_decode_logits_equal_the_reference(n, monkeypatch):
    """Logits, not tokens: the decode program's logits for a slot after n
    prompt tokens and j steps are the reference's at position n + j."""
    lm = _lm()
    seen = []
    body = eng._decode_step_body

    def spy(*a, **kw):
        logits, kv = body(*a, **kw)
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return logits, kv

    monkeypatch.setattr(eng, "_decode_step_body", spy)
    server = DecodeServer(lm, slots=2, max_len=64, buckets=(16,))
    req = server.submit(_tokens(n, seed=n), 6)
    server.drain()
    seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])[:-1]
    want = np.asarray(ref.forward_tail(lm.params, seq, CFG, 5))
    got = np.stack([s[req.slot] for s in seen[:5]])
    np.testing.assert_allclose(got, want, atol=2 * TOL)


@pytest.mark.parametrize("n", [5, 16, 37, 2])     # buckets 16, 16, 64, 16
def test_bucket_padded_prefill_leaves_the_unpadded_state(n):
    lm = _lm()
    server = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 64))
    server.engine.prefill(_tokens(n, seed=n), 1, jax.random.PRNGKey(0))
    toks = jnp.asarray(_tokens(n, seed=n))[None]
    h = jnp.take(lm.params["embed"], toks, axis=0)
    for i, blk in enumerate(lm.params["blocks"]):
        h, s, z = lm._block(blk, h)
        np.testing.assert_allclose(server.engine.cache.kda[i][1], s[0],
                                   atol=1e-5)
        np.testing.assert_allclose(server.engine.cache.norm[i][1], z[0],
                                   atol=1e-5)
        assert not np.asarray(server.engine.cache.kda[i][0]).any()


def test_a_reused_slot_gives_the_fresh_servers_tokens():
    lm = _lm()
    lengths = [(40, 12), (7, 9), (21, 15)]
    server = DecodeServer(lm, slots=1, max_len=128, buckets=(16, 32, 64))
    reqs = [server.submit(_tokens(n, seed=n), k) for n, k in lengths]
    server.drain()
    assert [r.slot for r in reqs] == [0, 0, 0]
    for (n, k), r in zip(lengths, reqs):
        fresh = DecodeServer(lm, slots=1, max_len=128, buckets=(16, 32, 64))
        alone = fresh.submit(_tokens(n, seed=n), k)
        fresh.drain()
        assert r.tokens == alone.tokens


def test_a_slot_that_owes_nothing_keeps_its_state():
    lm = _lm()
    server = DecodeServer(lm, slots=2, max_len=64, buckets=(16,))
    short = server.submit(_tokens(5), 2)
    server.submit(_tokens(6, seed=1), 12)
    while short.state != "finished":
        server.step()
    server.flush()
    cache = server.engine.cache
    before = [np.asarray(a[short.slot]) for a in cache.kda + cache.norm]
    server.drain()
    cache = server.engine.cache
    after = [np.asarray(a[short.slot]) for a in cache.kda + cache.norm]
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert all(a.any() for a in before)


def test_the_pool_has_no_array_with_a_time_axis():
    """``pool_layout`` describes the state (float32 whatever ``kv_dtype``
    says), ``kv_pool_nbytes`` sums it, and ``max_len`` costs nothing."""
    lm = _lm()
    rows = ret.state_rows(DH)
    for kv_dtype in ("bfloat16", "float32"):
        layout = pool_layout(lm, 3, 128, kv_dtype)
        assert layout["recurrent"] == [((3, HKV, rows, DH), "float32")] * 2
        assert layout["normaliser"] == [((3, HKV, DH, DH), "float32")] * 2
        assert not any(layout[k] for k in ("kv", "ring", "latent", "index",
                                           "conv"))
    per_slot = 2 * HKV * (rows * DH + DH * DH) * 4
    assert kv_pool_nbytes(lm, 3, 128) == kv_pool_nbytes(lm, 3, 32768) \
        == 3 * per_slot
    assert max_slots_in_budget(lm, 32768, 10 * per_slot + 1) == 10
    cache = SlotKVCache(lm, 3, 128)
    assert cache.nbytes == 3 * per_slot and cache.k is None
    assert cache.nbytes_by_kind == {
        "kv": 0, "latent": 0, "recurrent": 3 * 2 * HKV * rows * DH * 4,
        "conv": 0, "normaliser": 3 * 2 * HKV * DH * DH * 4}
    assert set(cache.state) == {"kda", "norm"}


def test_stats_and_spans_count_state_slots_and_no_rows():
    lm = _lm()
    spans = []

    def sink(span):
        if span["name"] == "serve.decode" and span["attrs"].get("live"):
            spans.append(span["attrs"])

    program_trace.add_sink(sink)
    try:
        server, reqs = _served(lm, [(5, 9), (16, 5), (37, 7)])
    finally:
        program_trace.remove_sink(sink)
    stats = server.stats()
    assert stats["kv_rows"] == 0 and stats["kv_blocks_share"] is None
    assert stats["state_slots"] == stats["decode_tokens"] == (
        sum(len(r.tokens) - 1 for r in reqs))
    assert stats["state_bytes"]["kv"] == 0
    assert stats["state_bytes"]["recurrent"] > 0 < (
        stats["state_bytes"]["normaliser"])
    assert spans and all(a["kv_rows"] == 0 and a["state_slots"] == a["live"]
                         for a in spans)
    assert "kv_blocks" not in spans[0]


def test_a_layer_of_another_kind_keeps_its_state_beside():
    """A 'gdn' layer's matrix and a 'ret' layer's state share the recurrent
    list in the layers' order; the tails and the normalisers are lists of
    their own."""
    lm = _lm(layers=3, mixers=("ret", "gdn", "ret"),
             gdn={"key_heads": 2, "value_heads": 4, "head_dim": 16,
                  "conv": 4})
    layout = pool_layout(lm, 2, 64, "bfloat16")
    assert [s[0][2] for s in layout["recurrent"]] == [
        ret.state_rows(DH), 16, ret.state_rows(DH)]
    assert len(layout["conv"]) == 1 and len(layout["normaliser"]) == 2
    server = DecodeServer(lm, slots=2, max_len=64, buckets=(16,))
    reqs = [server.submit(_tokens(n, seed=n), 6) for n in (5, 11, 7)]
    server.drain()
    for r in reqs:
        alone = DecodeServer(lm, slots=1, max_len=64, buckets=(16,))
        one = alone.submit(r.prompt, 6)
        alone.drain()
        assert r.tokens == one.tokens


# ---- (f) what cannot take the model says what is missing ---------------------
@pytest.mark.parametrize("what", ["mtp", "handoff", "mesh", "power",
                                  "learned_positions", "sequence_parallel",
                                  "sizes"])
def test_paths_without_the_new_state_refuse_the_model(what):
    if what == "mtp":
        with pytest.raises(ValueError, match="mtp= is written for a model "
                           "with RoPE whose last layer is 'mla'"):
            _lm(mtp={"loss_weight": 0.3})
    elif what == "handoff":
        server = DecodeServer(_lm(), slots=1, max_len=32, buckets=(16,))
        with pytest.raises(ValueError, match="hand-off carries K/V slabs "
                           "only.*'ret'"):
            handoff.export_slot(server.engine, 0)
    elif what == "mesh":
        with pytest.raises(ValueError, match="'ret' or 'mla' layers is "
                           "served on one chip: the mesh's head split"):
            SlotKVCache(_lm(), 1, 32, registry=object())
    elif what == "power":
        with pytest.raises(ValueError, match="written for degree 2"):
            _lm(ret={"power": 4})
    elif what == "learned_positions":
        with pytest.raises(ValueError, match="takes the model's RoPE"):
            _lm(pos_encoding="learned")
    elif what == "sequence_parallel":
        lm = _lm()
        with pytest.raises(NotImplementedError, match="sequence parallelism "
                           "is written for 'attn' layers"):
            lm._block(lm.params["blocks"][0], jnp.zeros((1, 4, D)),
                      sequence_parallel=True)
    else:
        with pytest.raises(ValueError, match="a 'ret' layer needs its sizes"):
            _lm(ret=None)
