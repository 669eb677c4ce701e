"""Qwen3-Next-80B-A3B's language model (Gated DeltaNet layers 3 : 1 with
gated softmax attention, softmax-routed experts beside a gated shared expert,
one chip's share of the experts) through ``TransformerLM`` and
``DecodeServer`` against the plain reference
(``benchmarks/lib/reference_qwen3_next.py``), at a small size with the
published model's proportions: hidden 64, 4 query / 2 kv heads of 32 (not 64
/ 4 = 16) with 8 rotary dimensions, 2 key / 4 value heads of 16 in the
recurrence, 4 taps, 16 experts of width 32, 4 a token, 4 held here, a gated
shared expert, vocabulary 256. float32 policy unless a test says otherwise.
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

from benchmarks.lib import reference_qwen3_next as ref  # noqa: E402
from deeplearning4j_tpu.models import gdn, routed_experts  # noqa: E402
from deeplearning4j_tpu.models.transformer import TransformerLM  # noqa: E402
from deeplearning4j_tpu.ops.attention import (  # noqa: E402
    grouped_query_attention)
from deeplearning4j_tpu.pallas.decode_attention import (  # noqa: E402
    pool_block_rows, pool_decode_attention)
from deeplearning4j_tpu.serving import (  # noqa: E402
    DecodeServer, SlotKVCache, kv_pool_nbytes, max_slots_in_budget)
from deeplearning4j_tpu.serving import engine as eng  # noqa: E402
from deeplearning4j_tpu.serving.fleet import handoff  # noqa: E402
from deeplearning4j_tpu.serving.kv_cache import (  # noqa: E402
    pool_layout, pool_shape)

V, D, H, HKV, DH, ROT, F, E, K, HELD = 256, 64, 4, 2, 32, 8, 32, 16, 4, 4
GDN = {"key_heads": 2, "value_heads": 4, "head_dim": 16, "conv": 4}
MIXERS = ("gdn", "gdn", "gdn", "attn")
TOL = 2e-5


def _cfg(first=0, held=HELD, **over):
    share = None if held is None else {"first_expert": first, "held": held}
    return {"rms_norm_eps": 1e-6, "rope_theta": 1e7,
            "num_attention_heads": H, "num_key_value_heads": HKV,
            "head_dim": DH, "rotary_dim": ROT,
            "linear_num_key_heads": GDN["key_heads"],
            "linear_num_value_heads": GDN["value_heads"],
            "linear_key_head_dim": GDN["head_dim"],
            "num_experts_per_tok": K, "share": share, **over}


def _lm(policy="float32", first=0, held=HELD, mixers=MIXERS, seed=3,
        d_model=D, d_ff=F, **over):
    n = len(mixers)
    kw = dict(
        vocab_size=V, d_model=d_model, num_heads=H, num_kv_heads=HKV,
        num_layers=n, d_ff=d_ff, max_len=256, pos_encoding="rope",
        dtype_policy=policy, attn_impl="xla", norm="rmsnorm",
        num_experts=E, experts_per_token=K, norm_topk_prob=True,
        tie_embeddings=False, seed=seed, rope_theta=1e7, norm_eps=1e-6,
        mixers=mixers, ffns=("moe",) * n, gdn=GDN,
        attn={"head_dim": DH, "rotary_dim": ROT, "head_norm": True,
              "gate": True},
        moe={"shared_width": d_ff, "shared_gate": True, "first": first,
             "held": held})
    kw.update(over)
    lm = TransformerLM(**kw).init()
    # zeros and ones would hide a gate that forgot its bias or a norm that
    # forgot its gain
    keys = jax.random.split(jax.random.PRNGKey(seed + 99), n)
    for blk, key in zip(lm.params["blocks"], keys):
        k = jax.random.split(key, 3)
        if "gdn" in blk:
            hv = GDN["value_heads"]
            blk["gdn"]["a_log"] = jnp.log(jax.random.uniform(
                k[0], (hv,), minval=1.0, maxval=16.0))
            # a gate with memory: softplus(a - 4) is a few hundredths
            blk["gdn"]["dt_bias"] = -4 + jax.random.normal(k[1], (hv,))
            blk["gdn"]["o_norm"]["g"] = 1 + 0.1 * jax.random.normal(
                k[2], (GDN["head_dim"],))
        else:
            for name, kk in (("q_norm", k[0]), ("k_norm", k[1])):
                blk["attn"][name]["g"] = 1 + 0.1 * jax.random.normal(
                    kk, blk["attn"][name]["g"].shape)
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
@pytest.mark.parametrize("t", [9, 70, 130])
def test_forward_logits_are_the_references(t):
    lm = _lm()
    toks = _tokens(t, seed=t)
    got = lm.forward(lm.params, jnp.asarray(toks)[None])[0]
    want = ref.tail_logits(lm.params, toks, _cfg(), t)
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("kind", ["gdn", "attn"])
def test_each_mixer_is_the_references(kind):
    """One block's mixer alone on a random normed input: what ``_block``
    adds to the residual stream before the experts."""
    lm = _lm()
    blk = lm.params["blocks"][0 if kind == "gdn" else 3]
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 40, D))
    if kind == "gdn":
        got, _, _ = gdn.gdn_mixer(x, blk["gdn"], dims=GDN, eps=1e-6)
        want, _ = ref.gdn_mixer(x[0], blk["gdn"], _cfg())
    else:
        # the block without its norm and experts: unit gain, no moe
        bare = {"ln1": {"g": jnp.ones((D,))}, "ln2": blk["ln2"],
                "attn": blk["attn"], "moe": blk["moe"]}
        h0 = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
        xn = lm._norm(h0, bare["ln1"])
        want = ref.attn_mixer(xn[0], blk["attn"], _cfg())
        full, _, _ = lm._block(bare, h0)
        after = h0 + want[None]
        y, _ = ref.expert_layer(
            ref._rmsnorm(after[0], blk["ln2"]["g"], 1e-6), blk["moe"],
            _cfg())
        got, want = full, after + y[None]
    np.testing.assert_allclose(got[0], want[0] if want.ndim == 3 else want,
                               atol=TOL)


@pytest.mark.parametrize("control", ["no_gate", "no_decay"])
def test_the_references_controls_change_its_logits(control):
    """What the benchmark's controls switch off is seen by the logits."""
    lm = _lm()
    toks = _tokens(60)
    want = np.asarray(ref.tail_logits(lm.params, toks, _cfg(), 60))
    off = np.asarray(ref.tail_logits(lm.params, toks, _cfg(control=control),
                                     60))
    assert np.abs(off - want).max() > 100 * TOL


def test_rope_turns_the_first_rotary_dims_only():
    lm = _lm()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, H, DH))
    got = lm._rope_head(x, jnp.arange(12))
    want = ref._rope_partial(x[0], 1e7, ROT)
    np.testing.assert_allclose(got[0], want, atol=1e-6)
    np.testing.assert_array_equal(got[..., ROT:], x[..., ROT:])
    assert float(jnp.abs(got[0, 1:, :, :ROT] - x[0, 1:, :, :ROT]).max()) > 0.1


# ---- (b) the experts: softmax router over a share, gated shared expert ------
def _moe(seed=2):
    return routed_experts.init_experts(
        jax.random.PRNGKey(seed), D, F, E, jnp.float32, shared_width=F,
        shared_gate=True)


def test_the_gated_shared_expert():
    """sigmoid(x . w_sg) E_shared(x): the layer with the shared expert less
    the layer without it."""
    p = _moe()
    x = jax.random.normal(jax.random.PRNGKey(7), (24, D))
    kw = dict(experts_per_token=K, norm_topk_prob=True)
    both, _ = routed_experts.routed_ffn(x, p, **kw)
    routed, _ = routed_experts.routed_ffn(
        x, {k: v for k, v in p.items() if k != "shared"}, **kw)
    np.testing.assert_allclose(both - routed, ref.shared_part(x, p),
                               atol=TOL)
    gate = jax.nn.sigmoid(x @ p["shared"]["gate"])
    assert float(gate.max() - gate.min()) > 0.05    # one number a token


@pytest.mark.parametrize("rows,form", [(24, "dense"), (40, "sorted"),
                                       (48, "row_blocks")])
def test_the_shares_add_up_to_the_uncut_layer(rows, form, monkeypatch):
    """Four chips hold four experts each. Their routed parts, and the gated
    shared expert counted once, are the uncut reference layer; in the dense
    form (24 rows), in the sorted one (40 rows, past a lowered threshold)
    and in the sorted one a block of rows at a time (48 rows in 3 blocks)."""
    monkeypatch.setattr(routed_experts, "DENSE_MAX_TOKENS", 32)
    if form == "row_blocks":
        monkeypatch.setattr(routed_experts, "ROW_BLOCK", 16)
    p = _moe()
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, D))
    kw = dict(experts_per_token=K, norm_topk_prob=True)
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
        assert info["load"].shape == (HELD,)
        pairs += int(info["load"].sum())
        want, _ = ref.expert_layer(x, {**mine, "shared": p["shared"]},
                                   _cfg(first=chip * HELD))
        np.testing.assert_allclose(with_shared, want, atol=TOL)
    assert pairs == rows * K                  # every pair landed on one chip
    uncut, _ = ref.expert_layer(x, p, _cfg(held=None))
    np.testing.assert_allclose(total + shared, uncut, atol=TOL)


def test_router_weights_are_renormalised_probabilities():
    p = _moe()
    x = jax.random.normal(jax.random.PRNGKey(9), (30, D))
    w, e = routed_experts.route(x, p["router"], K, True)
    w_ref, e_ref, lead, shortfall = ref.route(x, p, _cfg())
    np.testing.assert_array_equal(e, e_ref)
    np.testing.assert_allclose(w, w_ref, atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    assert not np.asarray(shortfall).any() and float(lead.min()) >= 0
    # a choice that swaps the k-th expert for the next one falls short by
    # the lead
    nxt = jax.lax.top_k(jax.nn.softmax(x @ p["router"]), K + 1)[1]
    swapped = jnp.concatenate([e[:, :K - 1], nxt[:, K:]], axis=1)
    np.testing.assert_allclose(ref.route(x, p, _cfg(), swapped)[3], lead,
                               atol=1e-6)


# ---- (c) prefill then decode through DecodeServer is the full forward ------
def _served(lm, lengths, **server_kw):
    server = DecodeServer(lm, slots=3, max_len=128, buckets=(16, 32, 64),
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


def test_prefill_then_decode_is_the_reference_forward():
    """n prompt tokens through the bucketed prefill, then k tokens one step
    at a time through the slot cache (K/V rows, recurrent state, tails),
    five requests over three slots: every token is the reference's argmax
    over the whole sequence."""
    lm = _lm()
    _, reqs = _served(lm, [(5, 9), (16, 5), (37, 20), (20, 7), (9, 12)])
    _judge(lm, reqs, _cfg(), 1e-5)


def test_wide_heads_are_served_from_a_pool_of_rows():
    """Heads of 256, wider than a lane tile: the pool is stored ``[L, S,
    T_max Hkv, Dh]`` (``kv_cache.pool_shape``), row ``t Hkv + h`` position t
    of kv head h, and prefill, decode writes and the read agree with the
    reference; a mesh keeps the five axes."""
    lm = _lm(attn={"head_dim": 256, "rotary_dim": 64, "head_norm": True,
                   "gate": True})
    server, reqs = _served(lm, [(5, 9), (37, 12), (16, 5)])
    cache = server.engine.cache
    assert cache.k.shape == cache.v.shape == (1, 3, 128 * HKV, 256)
    assert cache.pool_dims == (1, 3, 128, HKV, 256)
    _judge(lm, reqs, _cfg(head_dim=256, rotary_dim=64), 1e-5)
    assert pool_shape((1, 3, 128, HKV, 256)) == (1, 3, 128 * HKV, 256)
    assert pool_shape((1, 3, 128, HKV, 256), sharded=True) == (
        1, 3, 128, HKV, 256)
    assert pool_shape((4, 3, 128, HKV, 128)) == (4, 3, 128, HKV, 128)
    assert server.stats()["kv_rows"] == sum(
        sum(range(n + 1, n + k)) for n, k in [(5, 9), (37, 12), (16, 5)])


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
    want = np.asarray(ref.tail_logits(lm.params, seq, _cfg(), 5))
    got = np.stack([s[req.slot] for s in seen[:5]])
    np.testing.assert_allclose(got, want, atol=2 * TOL)


@pytest.mark.parametrize("n", [5, 16, 37, 2])     # buckets 16, 16, 64, 16
def test_bucket_padded_prefill_leaves_the_unpadded_state(n):
    """The slot's recurrent matrices after a prefill padded to its bucket
    are those of the reference over the real tokens, the other slot is
    untouched, and the K/V rows of the one attention layer are written up to
    the prompt's length."""
    lm = _lm()
    server = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 64))
    toks = _tokens(n, seed=n)
    server.engine.prefill(toks, 1, jax.random.PRNGKey(0))
    states = ref.final_states(lm.params, toks, _cfg())
    cache = server.engine.cache
    assert len(cache.kda) == len(cache.conv) == len(states) == 3
    for got, want in zip(cache.kda, states):
        np.testing.assert_allclose(got[1], want, atol=TOL)
        assert not np.asarray(got[0]).any()
    assert cache.k.shape == (1, 2, 128, HKV, DH)
    assert np.asarray(cache.k[0, 1, :n]).any()
    assert not np.asarray(cache.k[0, 0]).any()


def test_bf16_server_stays_within_the_benchmark_tolerance():
    """The cell's policy: bf16 compute from float32 weights, judged with the
    experts the programs chose (``record_routing``) as the benchmark's
    check does."""
    lm = _lm("bf16", seed=4, d_model=256, d_ff=64)
    server = DecodeServer(lm, slots=3, max_len=128, buckets=(16, 32, 64),
                          record_routing=True)
    req = server.submit(_tokens(30, seed=30), 16)
    server.drain()
    toks = np.asarray(req.tokens, np.int32)
    seq = np.concatenate([req.prompt, toks])[:-1]
    experts = np.concatenate([r[0] for r in req.routing], axis=1)
    assert experts.shape == (4, len(seq), K)
    logits, routes = ref.forward_tail(lm.params, seq, _cfg(), len(toks),
                                      chosen=experts)
    logits = np.asarray(logits)
    gap = (logits.max(-1) - logits[np.arange(len(toks)), toks]) \
        / np.abs(logits).max(-1)
    assert gap.max() <= 2 ** -5
    # 4 of 16 experts at width 256: a flipped near-tie is a larger share of
    # a probability than at 10 of 512 (the cell's route_gap is set on the chip)
    assert max(float(r[3].max()) for r in routes) <= 0.35


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
    before = [np.asarray(a[short.slot]) for a in
              server.engine.cache.kda + server.engine.cache.conv]
    server.drain()
    after = [np.asarray(a[short.slot]) for a in
             server.engine.cache.kda + server.engine.cache.conv]
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert any(a.any() for a in before)


# ---- (d) the pool read at 256-wide heads -------------------------------------
@pytest.mark.parametrize("live", ["all", "some"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_pool_kernel_at_head_dim_256_is_the_xla_read(dtype, tol, live):
    """The decode kernel (interpreted) over a pool of 2 kv heads of 256,
    8 queries a kv head, against ``grouped_query_attention`` over the
    layer's slab; slots that hold no request give zeros."""
    rng = np.random.default_rng(0)
    layers, s_, t_max, hkv, h, dh = 2, 4, 64, 2, 16, 256
    pool_k, pool_v = (jnp.asarray(
        rng.normal(size=(layers, s_, t_max, hkv, dh)), dtype)
        for _ in range(2))
    q = jnp.asarray(rng.normal(size=(s_, 1, h, dh)), dtype)
    positions = jnp.asarray([[0], [17], [40], [t_max - 1]], jnp.int32)
    mask = jnp.arange(t_max)[None, None, :] <= positions[:, :, None]
    want = grouped_query_attention(q, pool_k[1], pool_v[1], mask=mask)
    alive = None if live == "all" else jnp.asarray([True, False, True, True])
    got = pool_decode_attention(q, pool_k, pool_v, 1, positions,
                                block_rows=32, interpret=True, live=alive)
    if alive is not None:
        assert not np.asarray(got[1]).any()
        got, want = got[np.asarray(alive)], want[np.asarray(alive)]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    # the cell's pool: 1,024 rows a block, 512 positions at 2 kv heads
    assert pool_block_rows((1, 64, 32768, 2, 256), "bfloat16") == 1024


def test_the_mixtures_decode_step_reads_the_pool_by_the_kernel():
    """A recurrence beside a K/V pool in one decode program: the step with
    the pool read by the kernel (interpreted here) and by the XLA op gives
    the same logits, K/V rows and recurrent state. At 256-wide heads the
    pool is stored as its rows (``kv_cache.pool_shape``)."""
    lm = _lm(attn={"head_dim": 256, "rotary_dim": ROT, "head_norm": True,
                   "gate": True})
    rng = np.random.default_rng(1)
    slots, max_len = 3, 96
    kv = jax.tree_util.tree_map(
        lambda a: jnp.asarray(0.3 * rng.normal(size=a.shape), a.dtype),
        SlotKVCache(lm, slots, max_len).state)
    assert kv["k"].shape == (1, slots, max_len * HKV, 256)
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
    for a, b in zip(jax.tree_util.tree_leaves(got_kv),
                    jax.tree_util.tree_leaves(want_kv)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)
    # the slot that owes nothing keeps its recurrent state
    for new, old in zip(got_kv["kda"], kv["kda"]):
        np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(old[1]))
        assert float(jnp.abs(new[0] - old[0]).max()) > 0


# ---- (e) sizing --------------------------------------------------------------
@pytest.mark.parametrize("mixers", [MIXERS, ("attn", "gdn", "attn", "gdn"),
                                    ("gdn",) * 4])
def test_pool_bytes_count_every_kind(mixers):
    lm = _lm(mixers=mixers)
    slots, t = 3, 40
    cache = SlotKVCache(lm, slots, t, "bfloat16")
    n_gdn, n_attn = mixers.count("gdn"), mixers.count("attn")
    hv, dk, width = 4, 16, 2 * 2 * 16 + 4 * 16
    want = {"kv": 2 * n_attn * slots * t * HKV * DH * 2, "latent": 0,
            "recurrent": n_gdn * slots * hv * dk * dk * 4,
            "conv": n_gdn * slots * 3 * width * 2}
    assert cache.nbytes_by_kind == want
    assert cache.nbytes == sum(want.values()) \
        == kv_pool_nbytes(lm, slots, t, "bfloat16")
    assert max_slots_in_budget(lm, t, 10 * cache.per_slot_nbytes,
                               "bfloat16") == 10
    layout = pool_layout(lm, slots, t, "bfloat16")
    assert layout["recurrent"] == [((slots, hv, dk, dk), "float32")] * n_gdn
    assert layout["conv"] == [((slots, 3, width), "bfloat16")] * n_gdn
    assert layout["kv"] == ([((n_attn, slots, t, HKV, DH), "bfloat16")] * 2
                            if n_attn else [])
    assert set(cache.state) == (({"kda", "conv"} if n_gdn else set())
                                | ({"k", "v"} if n_attn else set()))


def test_a_kda_layer_and_a_gdn_layer_keep_state_side_by_side():
    """The two delta-rule mixers in one stack: one list of recurrent
    matrices, each layer's own shape, in the layers' order."""
    lm = TransformerLM(
        vocab_size=V, d_model=D, num_heads=H, num_layers=3, d_ff=96,
        max_len=64, pos_encoding="rope", attn_impl="xla", norm="rmsnorm",
        tie_embeddings=False, mixers=("kda", "gdn", "kda"),
        ffns=("mlp",) * 3, gdn=GDN,
        kda={"head_dim": 8, "conv": 3, "lower": -5.0}).init()
    layout = pool_layout(lm, 2, 32, "float32")
    assert [a[0] for a in layout["recurrent"]] == [
        (2, 4, 8, 8), (2, 4, 16, 16), (2, 4, 8, 8)]
    assert [a[0] for a in layout["conv"]] == [
        (2, 2, 96), (2, 3, 128), (2, 2, 96)]
    server = DecodeServer(lm, slots=2, max_len=32, buckets=(16,))
    req = server.submit(_tokens(9), 6)
    server.drain()
    seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
    logits = lm.forward(lm.params, jnp.asarray(seq[:-1])[None])[0]
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(logits[-6:], -1)), req.tokens)


def test_stats_and_spans_carry_what_the_readers_divide_by():
    """``kv_rows`` (the live slots' K/V rows up to their cursors, one
    attention layer) and ``state_slots`` (slots whose recurrent state a step
    moved) on every ``serve.decode`` span that dispatched, and summed in
    ``stats()``."""
    from deeplearning4j_tpu.monitor.trace import tracer

    tracer().clear()
    lm = _lm()
    server, reqs = _served(lm, [(5, 9), (16, 5), (37, 20)],
                           record_routing=True)
    spans = [s.attrs for s in tracer().spans()
             if s.name == "serve.decode" and s.attrs.get("live")]
    assert spans and all(s["state_slots"] == s["live"] for s in spans)
    assert all(s["live"] <= s["kv_rows"] <= s["live"] * 128 for s in spans)
    st = server.stats()
    assert st["kv_rows"] == sum(s["kv_rows"] for s in spans)
    assert st["state_slots"] == sum(s["state_slots"] for s in spans)
    # a request of n prompt tokens and k new ones decodes k - 1 steps, at
    # cursors n .. n + k - 2, each holding cursor + 1 rows
    want = sum(sum(range(n + 1, n + k)) for n, k in [(5, 9), (16, 5),
                                                     (37, 20)])
    assert st["kv_rows"] == want
    assert st["state_bytes"] == server.engine.cache.nbytes_by_kind
    assert st["state_bytes"]["kv"] > 0 < st["state_bytes"]["recurrent"]
    assert np.asarray(st["moe_expert_load"]).shape == (4, HELD)
    assert 0.5 < st["moe_pairs_here_per_token"] < 1.5


# ---- (f) the model as a TransformerLM ---------------------------------------
def test_forward_and_loss_differentiate():
    lm = _lm()
    toks = jnp.asarray(np.stack([_tokens(70), _tokens(70, seed=1)]))
    loss, grads = jax.value_and_grad(lm.loss)(lm.params, toks)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat)
    g0, g3 = grads["blocks"][0], grads["blocks"][3]
    for leaf in (g0["gdn"]["a_log"], g0["gdn"]["dt_bias"],
                 g0["gdn"]["w_ba"], g0["moe"]["shared"]["gate"],
                 g3["attn"]["q_norm"]["g"], g3["attn"]["wq"]):
        assert float(jnp.abs(leaf).max()) > 0


def test_a_train_step_lowers_the_loss():
    lm = _lm(lr=3e-3)
    toks = np.stack([_tokens(48), _tokens(48, seed=1)])
    first = lm.fit_batch(toks)
    for _ in range(4):
        last = lm.fit_batch(toks)
    assert last < first


def test_get_config_rebuilds_the_model():
    lm = _lm()
    again = TransformerLM(**lm.get_config())
    assert again.get_config() == lm.get_config()
    shapes = jax.eval_shape(lambda: again.init().params)
    assert jax.tree_util.tree_map(lambda a: a.shape, shapes) \
        == jax.tree_util.tree_map(lambda a: a.shape, lm.params)
    assert again.mixers == MIXERS and again.experts_held == HELD
    assert again.head_dim == DH and again.rotary_dim == ROT
    specs = lm.param_specs(model_axis_size=1)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, lm.params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, specs, is_leaf=lambda a: not isinstance(
                a, (dict, list))))


def test_the_attn_mixers_defaults_are_unchanged():
    """Without ``attn=`` the head is d_model // num_heads wide, every
    dimension turns, and ``wq`` is square."""
    lm = TransformerLM(vocab_size=64, d_model=32, num_heads=4, num_layers=1,
                       pos_encoding="rope", qk_norm=True,
                       norm="rmsnorm").init()
    a = lm.params["blocks"][0]["attn"]
    assert lm.head_dim == lm.rotary_dim == 8
    assert a["wq"].shape == a["wo"].shape == (32, 32)
    assert a["q_norm"]["g"].shape == (32,)


@pytest.mark.parametrize("bad", [
    dict(mixers=("gdn",), gdn=None),
    dict(mixers=("gdn",),
         gdn={"key_heads": 3, "value_heads": 4, "head_dim": 8, "conv": 4}),
    dict(mixers=("attn",), attn={"head_dim": 16, "rotary_dim": 5}),
    dict(mixers=("attn",), attn={"head_dim": 16, "rotary_dim": 32}),
    dict(mixers=("gdx",))])
def test_sizes_that_do_not_describe_a_layer_are_refused(bad):
    kw = dict(vocab_size=64, d_model=32, num_heads=4, num_layers=1,
              pos_encoding="rope", gdn=GDN)
    kw.update(bad)
    with pytest.raises(ValueError):
        TransformerLM(**kw)


@pytest.mark.parametrize("what", ["generate", "beam", "mesh", "handoff",
                                  "scan_layers", "sequence_parallel"])
def test_paths_without_the_new_state_refuse_the_model(what):
    """Every path that carries K/V only names what it lacks instead of
    decoding garbage."""
    lm = _lm()
    prompt = _tokens(5)[None]
    if what == "generate":
        with pytest.raises(NotImplementedError, match="'gdn' layer's "
                           "recurrent state"):
            lm.generate(prompt, 3)
    elif what == "beam":
        with pytest.raises(NotImplementedError, match="recurrent state"):
            lm.generate_beam(prompt, 3, beam_size=2)
    elif what == "mesh":
        from deeplearning4j_tpu.parallel.mesh import build_mesh

        with pytest.raises(ValueError, match="mesh's head split"):
            SlotKVCache(lm, 1, 32, registry=object())
        del build_mesh
    elif what == "handoff":
        server = DecodeServer(lm, slots=1, max_len=32, buckets=(16,))
        with pytest.raises(ValueError, match="hand-off"):
            handoff.export_slot(server.engine, 0)
    elif what == "scan_layers":
        cfg = dict(lm.get_config(), scan_layers=True)
        with pytest.raises(ValueError, match="scan_layers needs every "
                           "layer the same block"):
            TransformerLM(**cfg).init().forward(lm.params,
                                                jnp.asarray(prompt))
    else:
        with pytest.raises(NotImplementedError, match="sequence parallelism "
                           "is written for 'attn' layers"):
            lm._block(lm.params["blocks"][0], jnp.zeros((1, 4, D)),
                      sequence_parallel=True)
