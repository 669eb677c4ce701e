"""Mellum2-12B-A2.5B's language model (sliding-window attention layers 3 : 1
with full-attention layers, a RoPE per layer kind, 8 of 64 softmax-routed
experts) through ``TransformerLM`` and ``DecodeServer`` against the plain
reference (``benchmarks/lib/reference_mellum2.py``), at a small size with the
published model's proportions: hidden 48, 8 query / 2 kv heads of 16 (8 x 16
is not 48), a window of 16, YaRN x 16 over an original context of 32 on the
full layer, 16 experts of width 24, 4 a token, vocabulary 256. The served
ring holds 16 positions beside pools of 96 to 128, so a sequence of 60 laps
it more than three times. float32 policy unless a test says otherwise.
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

from benchmarks.lib import reference_mellum2 as ref  # noqa: E402
from deeplearning4j_tpu.models.transformer import TransformerLM  # noqa: E402
from deeplearning4j_tpu.ops.attention import (  # noqa: E402
    grouped_query_attention)
from deeplearning4j_tpu.pallas.decode_attention import (  # noqa: E402
    pool_decode_attention)
from deeplearning4j_tpu.serving import (  # noqa: E402
    DecodeServer, SlotKVCache, kv_pool_nbytes, max_slots_in_budget)
from deeplearning4j_tpu.serving import engine as eng  # noqa: E402
from deeplearning4j_tpu.serving.fleet import handoff  # noqa: E402
from deeplearning4j_tpu.serving.kv_cache import (  # noqa: E402
    attn_places, pool_layout, ring_positions, ring_rows)

V, D, H, HKV, DH, F, E, K, W = 256, 48, 8, 2, 16, 24, 16, 4, 16
KINDS = ("sliding_attention",) * 3 + ("full_attention",)
YARN = {"rope_type": "yarn", "rope_theta": 5e5, "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
ROPES = {"full_attention": YARN,
         "sliding_attention": {"rope_type": "default", "rope_theta": 5e5}}
TOL = 2e-5


def _cfg(kinds=KINDS, **over):
    return {"rms_norm_eps": 1e-6, "num_attention_heads": H,
            "num_key_value_heads": HKV, "head_dim": DH, "sliding_window": W,
            "num_experts_per_tok": K, "layer_types": list(kinds),
            "rope_parameters": ROPES, **over}


def _attn(kinds=KINDS, **over):
    scaling = {k: YARN[k] for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow")}
    return {"head_dim": DH, "head_norm": True,
            "windows": tuple(W if k == "sliding_attention" else None
                             for k in kinds),
            "rope": {"window": {"theta": 5e5},
                     "full": {"theta": 5e5, "scaling": scaling}}, **over}


def _lm(policy="float32", kinds=KINDS, seed=3, **over):
    n = len(kinds)
    kw = dict(
        vocab_size=V, d_model=D, num_heads=H, num_kv_heads=HKV, num_layers=n,
        d_ff=F, max_len=256, pos_encoding="rope", dtype_policy=policy,
        attn_impl="xla", norm="rmsnorm", num_experts=E, experts_per_token=K,
        norm_topk_prob=True, tie_embeddings=False, seed=seed, norm_eps=1e-6,
        attn=_attn(kinds))
    kw.update(over)
    lm = TransformerLM(**kw).init()
    # unit gains would hide a norm that forgot its gain
    for blk, key in zip(lm.params["blocks"], jax.random.split(
            jax.random.PRNGKey(seed + 99), n)):
        for name, kk in zip(("q_norm", "k_norm"), jax.random.split(key)):
            g = blk["attn"][name]["g"]
            blk["attn"][name]["g"] = 1 + 0.1 * jax.random.normal(kk, g.shape)
    return lm


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, V, n).astype(np.int32)


@pytest.fixture(autouse=True)
def _highest(request):
    """float32 matmuls as written on both sides; the bf16 test runs the
    program at its own precision (the CPU has no bf16 dot at ``highest``)."""
    if "bf16" in request.node.name:
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


# ---- (a) the forward pass is the reference's --------------------------------
@pytest.mark.parametrize("t", [9, 40, 130])
def test_forward_logits_are_the_references(t):
    lm = _lm()
    toks = _tokens(t, seed=t)
    got = lm.forward(lm.params, jnp.asarray(toks)[None])[0]
    want = ref.tail_logits(lm.params, toks, _cfg(), t)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_loss_and_its_gradient_are_the_references():
    lm = _lm()
    toks = _tokens(70, seed=5)
    got, g_got = jax.value_and_grad(lm.loss)(lm.params,
                                             jnp.asarray(toks)[None])
    want, g_want = jax.value_and_grad(ref.loss)(lm.params, toks, _cfg())
    np.testing.assert_allclose(got, want, atol=TOL)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=TOL)
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(g_got))


@pytest.mark.parametrize("control", ["no_window", "one_rope", "stale_ring"])
def test_the_references_controls_change_its_logits(control):
    """Each control computes another model: a check that passes it does not
    see the window, the second RoPE or the ring's lap."""
    lm = _lm()
    toks = _tokens(70, seed=2)
    want = ref.tail_logits(lm.params, toks, _cfg(), 30)
    got = ref.tail_logits(lm.params, toks, _cfg(control=control), 30)
    assert float(jnp.abs(got - want).max()) > 1e-3


def test_the_references_rope_tables_are_its_own():
    """The reference's tables against the formulas written out: the sliding
    section is theta^(-2j/dh) with amplitude 1; the full section keeps the
    fast dimensions, divides the slow ones by the factor, and turns with
    ``attention_factor``."""
    inv, amp = ref.rope_table(DH, ROPES["sliding_attention"])
    np.testing.assert_allclose(inv, 5e5 ** (-np.arange(8) / 8), rtol=1e-6)
    assert amp == 1.0
    big = {**YARN, "original_max_position_embeddings": 8192}
    inv, amp = ref.rope_table(128, big)
    plain = 5e5 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)  # low = 18
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    assert plain[25] / 16 < inv[25] < plain[25]
    assert amp == pytest.approx(0.1 * np.log(16) + 1)


def test_a_layers_kind_picks_its_window_and_its_rope():
    """One block alone: the same parameters as a window layer and as a full
    layer differ, and each is the reference's layer of that kind."""
    lm = _lm()
    blk = lm.params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 40, D))
    outs = {}
    for layer, kind in ((0, "sliding_attention"), (3, "full_attention")):
        got, _, _ = lm._block(blk, x, layer=layer)
        cfg = _cfg()
        h = x[0] + ref.attn_mixer(ref._rmsnorm(x[0], blk["ln1"]["g"], 1e-6),
                                  blk["attn"], kind, cfg)
        y, _ = ref.expert_layer(ref._rmsnorm(h, blk["ln2"]["g"], 1e-6),
                                blk["moe"], cfg)
        np.testing.assert_allclose(got[0], h + y, atol=TOL)
        outs[kind] = got
    assert float(jnp.abs(outs["sliding_attention"]
                         - outs["full_attention"]).max()) > 1e-3
    with pytest.raises(ValueError, match="needs layer="):
        lm._block(blk, x)


# ---- (b) serving: a ring beside the pool -------------------------------------
def _served(lm, lengths, **server_kw):
    server = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 32, 64),
                          **server_kw)
    reqs = [server.submit(_tokens(n, seed=n), k) for n, k in lengths]
    server.drain()
    return server, reqs


def _judge(lm, reqs, cfg, tol):
    for r in reqs:
        toks = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([r.prompt, toks])[:-1]
        logits = np.asarray(ref.tail_logits(lm.params, seq, cfg, len(toks)))
        gap = (logits.max(-1) - logits[np.arange(len(toks)), toks]) \
            / np.abs(logits).max(-1)
        assert gap.max() <= tol, (len(r.prompt), gap.max())


LENGTHS = [(5, 60), (37, 40), (16, 5), (50, 30), (9, 12)]


def test_prefill_then_decode_is_the_reference_forward():
    """Prompts shorter and longer than the ring through the bucketed prefill,
    then up to 60 tokens one step at a time, five requests over two slots
    whose cursors differ: every token is the reference's argmax over the
    whole sequence, across more than three laps of the 16-row ring."""
    lm = _lm()
    server, reqs = _served(lm, LENGTHS)
    cache = server.engine.cache
    assert cache.ring == W and cache.kw.shape == (3, 2, W, HKV, DH)
    assert cache.k.shape == (1, 2, 128, HKV, DH)
    assert max(n + k for n, k in LENGTHS) > 3 * W
    _judge(lm, reqs, _cfg(), 1e-5)


@pytest.mark.parametrize("n,k", [(11, 40), (16, 36), (40, 22)])
def test_decode_logits_equal_the_reference(n, k, monkeypatch):
    """Logits, not tokens: the decode program's logits for a slot after n
    prompt tokens and j steps are the reference's at position n + j, while
    the other slot decodes at another cursor and the ring laps."""
    lm = _lm()
    seen = []
    body = eng._decode_step_body

    def spy(*a, **kw):
        logits, kv = body(*a, **kw)
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return logits, kv

    monkeypatch.setattr(eng, "_decode_step_body", spy)
    server = DecodeServer(lm, slots=2, max_len=96, buckets=(16, 64))
    other = server.submit(_tokens(23, seed=1), k + 8)
    req = server.submit(_tokens(n, seed=n), k)
    server.drain()
    assert n + k > 3 * W and other.slot != req.slot
    seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])[:-1]
    want = np.asarray(ref.tail_logits(lm.params, seq, _cfg(), k - 1))
    got = np.stack([s[req.slot] for s in seen[:k - 1]])
    np.testing.assert_allclose(got, want, atol=2 * TOL)


def test_a_ring_and_a_pool_of_t_max_rows_serve_the_same_logits(monkeypatch):
    """The same model served with its ring and with ``T_max`` rows for every
    layer (``ring=False``): the same tokens, and logits that agree to
    float32 rounding — the keys a query sees are the same, in another order
    of rows."""
    lm = _lm()
    seen = {True: [], False: []}
    body = eng._decode_step_body
    toks = {}
    for ring in (True, False):
        def spy(*a, _ring=ring, **kw):
            logits, kv = body(*a, **kw)
            jax.debug.callback(
                lambda x: seen[_ring].append(np.asarray(x)), logits)
            return logits, kv

        monkeypatch.setattr(eng, "_decode_step_body", spy)
        server, reqs = _served(lm, LENGTHS[:2], ring=ring)
        assert (server.engine.cache.kw is not None) == ring
        assert server.engine.cache.k.shape[0] == (1 if ring else 4)
        toks[ring] = [r.tokens for r in reqs]
    assert toks[True] == toks[False]
    assert len(seen[True]) == len(seen[False]) > 3 * W
    np.testing.assert_allclose(np.stack(seen[True]), np.stack(seen[False]),
                               atol=2e-6)


@pytest.mark.parametrize("n", [5, 16, 37, 64])   # buckets 16, 16, 64, 64
def test_bucket_padded_prefill_writes_the_ring_where_the_rows_belong(n):
    """After a prefill padded to its bucket, ring row r of a window layer
    holds the key of position ``(n - 1) - ((n - 1 - r) mod R)`` where that is
    not negative, as a ``T_max`` pool of the same layer holds it; the other
    slot is untouched."""
    lm = _lm()
    toks = _tokens(n, seed=n)
    caches = {}
    for ring in (True, False):
        server = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 64),
                              ring=ring)
        server.engine.prefill(toks, 1, jax.random.PRNGKey(0))
        caches[ring] = server.engine.cache
    held = ring_positions(np.asarray(n - 1), W)
    assert held.max() == n - 1 and (held >= 0).sum() == min(n, W)
    for r, t in enumerate(held):
        if t >= 0:      # the window layers are the flat pool's layers 0..2
            np.testing.assert_array_equal(
                np.asarray(caches[True].kw[:, 1, r]),
                np.asarray(caches[False].k[:3, 1, t]))
    np.testing.assert_array_equal(np.asarray(caches[True].k[0, 1, :n]),
                                  np.asarray(caches[False].k[3, 1, :n]))
    assert not np.asarray(caches[True].kw[:, 0]).any()


def test_bf16_server_stays_within_the_benchmark_tolerance():
    lm = _lm(policy="bf16")
    _, reqs = _served(lm, [(5, 40), (37, 30)])
    _judge(lm, reqs, _cfg(), 0.05)


def test_a_reused_slot_gives_the_fresh_servers_tokens():
    """A slot's ring after a request that lapped it is rewritten whole by
    the next prefill."""
    lm = _lm()
    server = DecodeServer(lm, slots=1, max_len=128, buckets=(16, 64))
    first = server.submit(_tokens(40, seed=1), 40)
    server.drain()
    again = server.submit(_tokens(9, seed=2), 30)
    server.drain()
    fresh = DecodeServer(lm, slots=1, max_len=128, buckets=(16, 64))
    want = fresh.submit(_tokens(9, seed=2), 30)
    fresh.drain()
    assert again.tokens == want.tokens and len(first.tokens) == 40


def test_the_hand_off_carries_the_ring():
    """A slot exported after its prefill and installed into another server
    decodes the tokens the first server would have."""
    lm = _lm()
    prompt = _tokens(37, seed=4)
    whole = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 64))
    want = whole.submit(prompt, 30)
    whole.drain()
    src = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 64))
    tok, key, _ = src.engine.prefill(prompt, 1, jax.random.PRNGKey(0))
    slabs = handoff.export_slot(src.engine, 1)
    assert set(slabs) == {"k", "v", "kw", "vw"}
    dst = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 64))
    pack = handoff.SlotHandoff(
        slabs=slabs, cursor=37, key=np.asarray(key), first_token=int(tok),
        kv_dtype=dst.engine.kv_dtype, max_len=128)
    key = handoff.install_slot(dst.engine, 0, pack)
    dst.engine.admit_slot(0, int(tok), 37, 29, key)
    got = [int(tok)]
    for _ in range(29):
        got.append(int(np.asarray(dst.engine.decode()[0])[0]))
    assert got == want.tokens


# ---- (c) the ring in the decode kernel --------------------------------------
@pytest.mark.parametrize("live", ["all", "some"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_pool_kernel_reads_a_ring(dtype, tol, live):
    """The decode kernel (interpreted) over a ring of 32 positions in two
    key blocks, 4 kv heads of 128, 8 queries a kv head, at cursors before
    the first lap, on a block's edge and laps in: against
    ``grouped_query_attention`` over the rows under the ring's mask."""
    rng = np.random.default_rng(0)
    layers, s_, ring, hkv, h, dh, window = 2, 5, 32, 4, 32, 128, 24
    pool_k, pool_v = (jnp.asarray(
        rng.normal(size=(layers, s_, ring, hkv, dh)), dtype)
        for _ in range(2))
    q = jnp.asarray(rng.normal(size=(s_, 1, h, dh)), dtype)
    positions = jnp.asarray([[0], [15], [31], [32], [1000]], jnp.int32)
    held = ring_positions(np.asarray(positions), ring)
    mask = jnp.asarray((held >= 0) & (held > np.asarray(positions)[..., None]
                                      - window))
    want = grouped_query_attention(q, pool_k[1], pool_v[1], mask=mask)
    alive = None if live == "all" else jnp.asarray(
        [True, False, True, True, True])
    got = pool_decode_attention(q, pool_k, pool_v, 1, positions,
                                window=window, block_rows=64, interpret=True,
                                live=alive, ring=True)
    if alive is not None:
        assert not np.asarray(got[1]).any()
        got, want = got[np.asarray(alive)], want[np.asarray(alive)]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    with pytest.raises(NotImplementedError, match="one query a slot"):
        pool_decode_attention(jnp.tile(q, (1, 2, 1, 1)), pool_k, pool_v, 1,
                              jnp.tile(positions, (1, 2)), window=window,
                              block_rows=64, interpret=True, ring=True)


def test_the_decode_step_reads_both_pools_by_the_kernel():
    """Heads of 128, where the kernel applies: the step with the ring and
    the pool read by the kernel (interpreted here) and by the XLA op gives
    the same logits and the same rows."""
    lm = _lm(attn=_attn(head_dim=128))
    rng = np.random.default_rng(1)
    slots, max_len = 3, 96
    kv = jax.tree_util.tree_map(
        lambda a: jnp.asarray(0.3 * rng.normal(size=a.shape), a.dtype),
        SlotKVCache(lm, slots, max_len).state)
    assert kv["kw"].shape == (3, slots, W, HKV, 128)
    positions = jnp.asarray([4, 60, max_len - 1])
    toks = jnp.asarray(rng.integers(1, V, slots), jnp.int32)
    live = jnp.asarray([True, False, True])
    (want, want_kv), (got, got_kv) = (
        eng._decode_step_body(lm, lm.params, kv, toks, positions,
                              pool_kernel=kernel, live=live)
        for kernel in (False, True))
    rows = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows],
                               rtol=1e-4, atol=1e-4)
    # the slot that owes nothing reads zeros by the kernel and garbage by
    # the XLA op: what it writes in the layers after the first differs
    for a, b in zip(jax.tree_util.tree_leaves(got_kv),
                    jax.tree_util.tree_leaves(want_kv)):
        np.testing.assert_allclose(np.asarray(a)[:, rows],
                                   np.asarray(b)[:, rows], rtol=1e-4,
                                   atol=1e-4)


def test_a_window_layers_kernel_is_named_by_its_scope(monkeypatch):
    """The decode program of a model that gives a window a layer opens
    ``attn.window`` round the window layers' pool reads and nothing round
    the full layer's; a model with the one ``attn_window`` opens none."""
    from jax._src import source_info_util

    from deeplearning4j_tpu.pallas import decode_attention

    seen = []
    real = decode_attention.pl.pallas_call

    def pallas_call(*a, **kw):
        call = real(*a, **kw)

        def run(*operands):
            seen.append(str(source_info_util.current_name_stack()))
            return call(*operands)
        return run

    monkeypatch.setattr(decode_attention.pl, "pallas_call", pallas_call)

    def lower(lm):
        del seen[:]
        kv = SlotKVCache(lm, 2, 64).state
        jax.jit(lambda p, kv, t, c: eng._decode_step_body(
            lm, p, kv, t, c, pool_kernel=True)).lower(
                lm.params, kv, jnp.zeros(2, jnp.int32),
                jnp.zeros(2, jnp.int32))
        return ["attn.window" in names for names in seen]

    assert lower(_lm(attn=_attn(head_dim=128))) == [True, True, True, False]
    plain = TransformerLM(
        vocab_size=V, d_model=256, num_heads=2, num_kv_heads=2, num_layers=2,
        d_ff=F, max_len=64, pos_encoding="rope", attn_window=W).init()
    assert lower(plain) == [False, False]


# ---- (d) sizing ---------------------------------------------------------------
def test_pool_bytes_count_the_ring():
    lm = _lm()
    slots, max_len = 3, 128
    layout = pool_layout(lm, slots, max_len, "float32")
    assert layout["kv"] == [((1, slots, max_len, HKV, DH), "float32")] * 2
    assert layout["ring"] == [((3, slots, W, HKV, DH), "float32")] * 2
    row = 2 * HKV * DH * 4
    want = slots * row * (max_len + 3 * W)
    assert kv_pool_nbytes(lm, slots, max_len, "float32") == want
    cache = SlotKVCache(lm, slots, max_len, "float32")
    assert cache.nbytes == want
    assert cache.nbytes_by_kind["ring"] == slots * row * 3 * W
    assert cache.nbytes_by_kind["kv"] == slots * row * max_len
    flat = SlotKVCache(lm, slots, max_len, "float32", ring=False)
    assert flat.nbytes == slots * row * 4 * max_len == kv_pool_nbytes(
        lm, slots, max_len, "float32", ring=False)
    assert "ring" not in flat.nbytes_by_kind
    assert max_slots_in_budget(lm, max_len, 10 * want // slots,
                               "float32") == 10
    assert attn_places(lm, True) == [("ring", 0), ("ring", 1), ("ring", 2),
                                     ("kv", 0)]
    assert attn_places(lm, False) == [("kv", i) for i in range(4)]


def test_the_ring_is_the_window_in_whole_kernel_blocks():
    """At the published sizes (4 kv heads of 128, a window of 1,024) a key
    block is 512 positions in bf16 and 256 in float32: the ring is 1,024
    rows either way. A window off the blocks is rounded up; a ring as long as
    the pool is no ring; a model with the one ``attn_window`` keeps none."""
    def model(window, **kw):
        return TransformerLM(
            vocab_size=V, d_model=64, num_heads=8, num_kv_heads=4,
            num_layers=2, max_len=64, pos_encoding="rope",
            attn={"head_dim": 128, "windows": (window, None)}, **kw)

    assert ring_rows(model(1024), 32768, "bfloat16") == 1024
    assert ring_rows(model(1024), 32768, "float32") == 1024
    assert ring_rows(model(1000), 32768, "bfloat16") == 1024
    assert ring_rows(model(1025), 32768, "bfloat16") == 1536
    assert ring_rows(model(1024), 1024, "bfloat16") is None
    one = TransformerLM(vocab_size=V, d_model=64, num_heads=8, num_layers=2,
                        max_len=64, pos_encoding="rope", attn_window=16)
    assert ring_rows(one, 4096, "float32") is None
    assert SlotKVCache(one.init(), 2, 64).kw is None


def test_stats_and_spans_count_each_pools_rows():
    """``kv_rows_window`` (``min(c + 1, window)`` a window layer) and
    ``kv_rows_full`` (``c + 1`` the full layer) on every ``serve.decode``
    span that dispatched and summed in ``stats()``; ``kv_rows`` is their
    sum; the ring's bytes have a kind of their own."""
    from deeplearning4j_tpu.monitor.trace import tracer

    tracer().clear()
    lm = _lm()
    lengths = [(5, 30), (37, 20)]
    server, _ = _served(lm, lengths)
    spans = [s.attrs for s in tracer().spans()
             if s.name == "serve.decode" and s.attrs.get("live")]
    st = server.stats()
    # a request of n prompt tokens and k new ones decodes k - 1 steps, at
    # cursors n .. n + k - 2, each holding cursor + 1 rows
    full = sum(sum(range(n + 1, n + k)) for n, k in lengths)
    window = 3 * sum(sum(min(c, W) for c in range(n + 1, n + k))
                     for n, k in lengths)
    assert st["kv_rows_full"] == full == sum(s["kv_rows_full"] for s in spans)
    assert st["kv_rows_window"] == window == sum(
        s["kv_rows_window"] for s in spans)
    assert st["kv_rows"] == full + window
    assert all(s["kv_rows"] == s["kv_rows_full"] + s["kv_rows_window"]
               for s in spans)
    assert st["state_bytes"] == server.engine.cache.nbytes_by_kind
    assert st["state_bytes"]["ring"] == 2 * 3 * 2 * W * HKV * DH * 4
    # a model with one window keeps the one count
    one = TransformerLM(vocab_size=V, d_model=D, num_heads=4, num_layers=2,
                        max_len=64, pos_encoding="rope", attn_window=8).init()
    plain = DecodeServer(one, slots=2, max_len=64, buckets=(16,))
    plain.submit(_tokens(5), 12)
    plain.drain()
    assert "kv_rows_window" not in plain.stats()
    assert plain.stats()["kv_rows"] == 2 * sum(
        min(c, 8) for c in range(6, 17))


def test_kv_blocks_count_both_pools():
    """Heads of 128 (the kernel's blocks apply): a step's ``kv_blocks`` are
    each pool's blocks times its layers, a ring's ``min(c + 1, R)`` rows in
    blocks whatever the cursor."""
    lm = _lm(attn=_attn(head_dim=128))
    server = DecodeServer(lm, slots=2, max_len=4096, buckets=(16,))
    reads = sorted(server._kv_reads, key=lambda r: r[0])
    assert [r[:5] for r in reads] == [(1, None, 4096, HKV, False),
                                      (3, W, W, HKV, True)]
    server._cursors[:] = [5, 3000]
    attrs = server._book_kv_blocks({1: None})
    full_block = reads[0][5] // HKV         # positions a block of the pool
    assert attrs["kv_blocks"] == (3000 // full_block + 1) + 3 * 1
    assert attrs["kv_blocks_pool"] == attrs["kv_blocks"] + 1 + 3 * 1
    assert attrs["kv_rows_window"] == 3 * W
    assert attrs["kv_rows_full"] == 3001


# ---- (e) the model as a TransformerLM ----------------------------------------
def test_a_train_step_lowers_the_loss():
    lm = _lm(lr=3e-3)
    toks = np.stack([_tokens(40, seed=s) for s in range(4)])
    first = lm.fit_batch(toks)
    for _ in range(5):
        last = lm.fit_batch(toks)
    assert last < first


def test_remat_recomputes_each_layer_as_its_own_kind():
    lm, again = _lm(), _lm(remat=True)
    toks = jnp.asarray(_tokens(40))[None]
    want, g_want = jax.value_and_grad(lm.loss)(lm.params, toks)
    got, g_got = jax.value_and_grad(again.loss)(lm.params, toks)
    np.testing.assert_allclose(got, want, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_get_config_rebuilds_the_model():
    import json

    lm = _lm()
    again = TransformerLM(**json.loads(json.dumps(lm.get_config())))
    assert again.windows == lm.windows == (W, W, W, None)
    assert again.rope_by_kind == lm.rope_by_kind
    again.params = lm.params
    toks = jnp.asarray(_tokens(40))[None]
    np.testing.assert_array_equal(again.forward(again.params, toks),
                                  lm.forward(lm.params, toks))


def test_generate_honours_the_layers_windows_and_ropes():
    """``generate()``'s own cache: the greedy tokens are the server's."""
    lm = _lm()
    prompt = _tokens(21, seed=8)
    out = np.asarray(lm.generate(prompt[None], 40))[0, 21:]
    server = DecodeServer(lm, slots=1, max_len=128, buckets=(32,))
    got = server.submit(prompt, 40)
    server.drain()
    assert out.tolist() == got.tokens


def test_one_window_for_every_layer_is_unchanged():
    """A model that gives ``attn_window`` keeps its constructor arguments,
    its parameter tree and its one pool; ``windows`` repeats the window."""
    lm = TransformerLM(vocab_size=V, d_model=D, num_heads=4, num_layers=3,
                       max_len=64, pos_encoding="rope", attn_window=8).init()
    assert lm.windows == (8, 8, 8) and not lm.by_layer
    assert lm.get_config()["attn"] is None
    assert set(SlotKVCache(lm, 2, 64).state) == {"k", "v"}
    same = TransformerLM(vocab_size=V, d_model=D, num_heads=4, num_layers=3,
                         max_len=64, pos_encoding="rope",
                         attn={"windows": (8, 8, 8)}).init()
    same.params = lm.params
    toks = jnp.asarray(_tokens(30))[None]
    np.testing.assert_array_equal(same.forward(same.params, toks),
                                  lm.forward(lm.params, toks))


@pytest.mark.parametrize("bad,match", [
    (dict(attn={"windows": (W, None)}), "for each of the 4 layers"),
    (dict(attn={"windows": (W, W, W, 0)}), "window >= 1 or None"),
    (dict(attn={"windows": (W, W, W, None)}, attn_window=8),
     "in place of attn_window"),
    (dict(attn={"windows": (W, None, None, None)},
          mixers=("gdn", "attn", "attn", "attn"),
          gdn={"key_heads": 2, "value_heads": 2, "head_dim": 8, "conv": 4}),
     "no 'attn' layer"),
    (dict(attn={"rope": {"sliding": {"theta": 1e4}}}), "'window' and 'full'"),
    (dict(attn={"rope": {"full": {"theta": 1e4, "scaling": {
        "rope_type": "linear", "factor": 2}}}}), "YaRN"),
    (dict(attn={"rope": {"full": {"theta": 1e4}}}, pos_encoding="rope",
          scan_layers=True), None),
])
def test_descriptions_that_fit_no_layer_are_refused(bad, match):
    kw = dict(vocab_size=V, d_model=D, num_heads=4, num_layers=4,
              max_len=64, pos_encoding="rope")
    kw.update(bad)
    if match is None:       # refused where the layers are run
        lm = TransformerLM(**kw).init()
        with pytest.raises(ValueError, match="every layer the same block"):
            lm.forward(lm.params, jnp.zeros((1, 8), jnp.int32))
        return
    with pytest.raises(ValueError, match=match):
        TransformerLM(**kw)


@pytest.mark.parametrize("what", ["mesh", "mtp", "rounds"])
def test_paths_that_cannot_carry_a_ring_refuse_the_model(what):
    """Each thing that cannot serve a model described by layer names what is
    missing."""
    lm = _lm()
    if what == "mesh":
        with pytest.raises(ValueError, match="ring of rows beside it"):
            SlotKVCache(lm, 1, 64, registry=object())
    elif what == "mtp":
        with pytest.raises(ValueError, match="description by layer"):
            TransformerLM(
                vocab_size=V, d_model=D, num_heads=4, num_layers=2,
                max_len=64, pos_encoding="rope", mixers=("attn", "mla"),
                mla={"kv_lora_rank": 16, "qk_nope_head_dim": 8,
                     "qk_rope_head_dim": 8, "v_head_dim": 8},
                attn={"windows": (8, None)}, mtp={"loss_weight": 0.1})
    else:
        kv = SlotKVCache(lm, 2, 64).state
        with pytest.raises(NotImplementedError, match="one query a slot"):
            eng._pool_attention(lm, dict(kv), jnp.zeros((2, 2), jnp.int32),
                                False)
