"""Ling-3.0-flash-VL's language model (KDA and MLA layers, group-limited
sigmoid routing with a shared expert, one chip's share of the experts)
through ``TransformerLM`` and ``DecodeServer`` against the plain reference
(``benchmarks/lib/reference_ling.py``), at a small size with the published
model's proportions: hidden 64, 4 heads of 16, a latent of 32 with 8 RoPE
dimensions, 16 experts of width 32 in 4 groups (2 kept), 4 a token, 4 held
here, a shared expert, a leading dense SwiGLU layer, an MLA layer closing a
run of KDA layers, vocabulary 256. float32 policy unless a test says
otherwise; ``docs/ling_hybrid.md`` has the equations.
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

from benchmarks.lib import reference_ling as ref  # noqa: E402
from deeplearning4j_tpu.models import kda, mla, routed_experts  # noqa: E402
from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerLM, _rmsnorm, _rope)
from deeplearning4j_tpu.ops.attention import (  # noqa: E402
    dot_product_attention)
from deeplearning4j_tpu.serving import (  # noqa: E402
    DecodeServer, SlotKVCache, kv_pool_nbytes, max_slots_in_budget)
from deeplearning4j_tpu.serving import engine as eng  # noqa: E402
from deeplearning4j_tpu.serving.fleet import handoff  # noqa: E402

V, D, H, DK, F, E, K, HELD = 256, 64, 4, 16, 32, 16, 4, 4
MLA = {"kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16}
MIXERS = ("kda", "kda", "mla", "kda")
# float32 on both sides: the program's chunked scan, absorbed attention and
# batched experts differ from the reference's recurrence, expanded keys and
# expert loop in the order of their sums only
TOL = 1e-5


def _cfg(first=0, held=HELD, **over):
    share = None if held is None else {"first_expert": first, "held": held}
    return {"num_attention_heads": H, "rms_norm_eps": 1e-6,
            "rope_theta": 6e6, **MLA, "num_experts_per_tok": K, "n_group": 4,
            "topk_group": 2, "routed_scaling_factor": 2.5,
            "kda_lower_bound": -5.0, "share": share, **over}


def _lm(policy="float32", first=0, held=HELD, mixers=MIXERS, seed=3,
        d_model=D, d_ff=F):
    n = len(mixers)
    lm = TransformerLM(
        vocab_size=V, d_model=d_model, num_heads=H, num_layers=n, d_ff=d_ff,
        max_len=256, pos_encoding="rope", dtype_policy=policy,
        attn_impl="xla", norm="rmsnorm", num_experts=E, experts_per_token=K,
        norm_topk_prob=True, tie_embeddings=False, seed=seed,
        rope_theta=6e6, rope_interleaved=True, norm_eps=1e-6,
        mixers=mixers, ffns=("glu",) + ("moe",) * (n - 1), glu_width=96,
        kda={"head_dim": DK, "conv": 4, "lower": -5.0}, mla=MLA,
        moe={"n_group": 4, "topk_group": 2, "scale": 2.5, "bias": True,
             "shared_width": d_ff, "first": first, "held": held}).init()
    # zeros and ones would hide a gate that forgot its bias or a norm that
    # forgot its gain
    keys = jax.random.split(jax.random.PRNGKey(seed + 99), n)
    for blk, key in zip(lm.params["blocks"], keys):
        k = jax.random.split(key, 3)
        if "kda" in blk:
            blk["kda"]["a_log"] = 0.5 * jax.random.normal(k[0], (H,))
            blk["kda"]["dt_bias"] = jax.random.normal(k[1], (H * DK,))
            blk["kda"]["o_norm"]["g"] = 1 + 0.1 * jax.random.normal(
                k[2], (DK,))
        elif "mla" in blk:
            blk["mla"]["kv_norm"]["g"] = 1 + 0.1 * jax.random.normal(
                k[0], (MLA["kv_lora_rank"],))
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


# ---- (a) the chunked scan is the recurrence --------------------------------
@pytest.mark.parametrize("t", [1, 7, 64, 65, 128, 200])
def test_kda_chunked_prefill_is_the_recurrence(t):
    """Lengths below, at, above and between multiples of the 64 positions
    of a chunk: the mixer's output at every position and its final state."""
    lm = _lm()
    p = lm.params["blocks"][0]["kda"]
    x = jax.random.normal(jax.random.PRNGKey(t), (1, t, D))
    y, s, _ = kda.kda_mixer(x, p, num_heads=H, lower=-5.0)
    y_ref, s_ref = ref.kda_mixer(x[0], p, _cfg())
    np.testing.assert_allclose(y[0], y_ref, atol=TOL)
    np.testing.assert_allclose(s[0], s_ref, atol=TOL)


def test_kda_decay_leaves_float32_range_inside_a_chunk():
    """With every gate at its lower bound a chunk's cumulated log-decay is
    -320: exp(+320) has no float32, so the scan may only ever form
    differences of cumulated decays."""
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(key[i], (1, 128, H, DK)) for i in range(3))
    g = jnp.full((1, 128, H, DK), -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(key[3], (1, 128, H)))
    o, s = kda.kda_scan(q, k, v, g, beta, jnp.zeros((1, H, DK, DK)))
    o_ref, s_ref = ref.kda_recurrence(q[0], k[0], v[0], g[0], beta[0])
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o[0], o_ref, atol=TOL)
    np.testing.assert_allclose(s[0], s_ref, atol=TOL)


# ---- (b) a padded prefill leaves the unpadded one's state -------------------
@pytest.mark.parametrize("n", [5, 16, 37, 2])     # buckets 16, 16, 64, 16
def test_bucket_padded_prefill_leaves_the_unpadded_state(n):
    """The slot's recurrent matrices and convolution tails after a prefill
    padded to its bucket are those of the reference over the real tokens
    (and of an unpadded ``kda_mixer``): pad rows take beta = 0 and g = 0
    and the tail is the last three REAL positions (fewer than three: zeros
    in front)."""
    lm = _lm()
    server = DecodeServer(lm, slots=2, max_len=128, buckets=(16, 64))
    toks = _tokens(n, seed=n)
    server.engine.prefill(toks, 1, jax.random.PRNGKey(0))
    states = ref.final_states(lm.params, toks, _cfg())
    cache = server.engine.cache
    assert len(cache.kda) == len(states) == 3
    for got, want in zip(cache.kda, states):
        np.testing.assert_allclose(got[1], want, atol=TOL)
        assert not np.asarray(got[0]).any()        # the other slot: untouched
    # the tail: the mixer on the unpadded prompt's first layer input
    h = jnp.take(lm.params["embed"], jnp.asarray(toks), axis=0)[None]
    x = lm._norm(h, lm.params["blocks"][0]["ln1"])
    _, _, tail = kda.kda_mixer(x, lm.params["blocks"][0]["kda"], num_heads=H,
                               lower=-5.0)
    np.testing.assert_allclose(cache.conv[0][1], tail[0], atol=TOL)
    assert tail.shape == (1, 3, 3 * H * DK)


# ---- (c) prefill then decode through DecodeServer is the full forward ------
def _served(lm, lengths, **server_kw):
    server = DecodeServer(lm, slots=3, max_len=128, buckets=(16, 32, 64),
                          **server_kw)
    reqs = [server.submit(_tokens(n, seed=n), k) for n, k in lengths]
    server.drain()
    return server, reqs


def _judge(lm, reqs, cfg, tol):
    """Every generated token against the reference's teacher-forced logits
    over prompt + generated: the argmax, or within ``tol`` x max|logit|."""
    for r in reqs:
        toks = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([r.prompt, toks])[:-1]
        logits = np.asarray(ref.tail_logits(lm.params, seq, cfg, len(toks)))
        gap = (logits.max(-1) - logits[np.arange(len(toks)), toks]) \
            / np.abs(logits).max(-1)
        assert gap.max() <= tol, (len(r.prompt), gap.max())


def test_prefill_then_decode_is_the_reference_forward():
    """n prompt tokens through the bucketed prefill, then k tokens one step
    at a time through the slot cache (latent rows, recurrent state, tails),
    five requests over three slots: every token is the reference's argmax
    over the whole sequence."""
    lm = _lm()
    _, reqs = _served(lm, [(5, 9), (16, 5), (37, 20), (20, 7), (9, 12)])
    _judge(lm, reqs, _cfg(), 1e-5)


def test_decode_logits_equal_the_reference(monkeypatch):
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
    req = server.submit(_tokens(11), 6)
    server.drain()
    seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])[:-1]
    want = np.asarray(ref.tail_logits(lm.params, seq, _cfg(), 5))
    got = np.stack([s[req.slot] for s in seen[:5]])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_bf16_server_stays_within_the_benchmark_tolerance():
    """The cell's policy: bf16 compute from float32 weights, judged with the
    experts the programs chose (``record_routing``) as the benchmark's
    check does."""
    lm = _lm("bf16")
    server = DecodeServer(lm, slots=3, max_len=128, buckets=(16, 32, 64),
                          record_routing=True)
    req = server.submit(_tokens(30), 16)
    server.drain()
    toks = np.asarray(req.tokens, np.int32)
    seq = np.concatenate([req.prompt, toks])[:-1]
    experts = np.concatenate([r[0] for r in req.routing], axis=1)
    assert experts.shape == (3, len(seq), K)
    logits, routes = ref.forward_tail(lm.params, seq, _cfg(), len(toks),
                                      chosen=experts)
    logits = np.asarray(logits)
    gap = (logits.max(-1) - logits[np.arange(len(toks)), toks]) \
        / np.abs(logits).max(-1)
    assert gap.max() <= 2 ** -5
    assert max(float(r[3].max()) for r in routes) <= 0.25


# ---- (d) absorbed decode attention is the expanded form --------------------
def test_mla_absorbed_decode_is_the_unabsorbed_form():
    """Queries at positions 9, 10, 11 of two rows against their cached
    latent rows, absorbed (scores against the latents themselves), equal
    ``attend_full`` over the same sequence, and the reference's mixer."""
    lm = _lm()
    p = lm.params["blocks"][2]["mla"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, D))
    pos = jnp.arange(12)
    qn, qr, latent, gate = mla.mla_project(
        x, p, num_heads=H, dims=MLA,
        rope=lambda a: _rope(a, pos, 6e6, True),
        rmsnorm=lambda a, g: _rmsnorm(a, g, 1e-6))
    full = mla.attend_full(
        qn, qr, latent, p, dims=MLA,
        attention=lambda q, k, v, scale: dot_product_attention(
            q, k, v, causal=True, scale=scale))
    rows = jnp.pad(latent, ((0, 0), (0, 20), (0, 0)))       # a longer cache
    mask = jnp.arange(32)[None, None, :] <= jnp.arange(9, 12)[None, :, None]
    absorbed = mla.attend_latent(qn[:, 9:], qr[:, 9:], rows,
                                 jnp.broadcast_to(mask, (2, 3, 32)), p,
                                 dims=MLA)
    np.testing.assert_allclose(absorbed, full[:, 9:], atol=TOL)
    y = mla.mla_output(full, gate, p)
    for b in range(2):
        np.testing.assert_allclose(y[b], ref.mla_mixer(x[b], p, _cfg()),
                                   atol=TOL)


def test_rope_pairing_and_base_come_from_the_model():
    """Interleaved pairs (2i, 2i+1) against the reference's; the default
    pairing (i, i + d/2) and base 10,000 are StarCoder2's and unchanged."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 2, 8))
    got = _rope(x, jnp.arange(9), 6e6, True)
    np.testing.assert_allclose(got[0], ref._rope_interleaved(x[0], 6e6),
                               atol=1e-6)
    half = _rope(x, jnp.arange(9))
    assert not np.allclose(half, got)
    np.testing.assert_allclose(half, _rope(x, jnp.arange(9), 10000.0, False))


# ---- (e) the router --------------------------------------------------------
def _moe(seed=0):
    p = routed_experts.init_experts(
        jax.random.PRNGKey(seed), D, F, E, jnp.float32, bias=True,
        shared_width=F)
    p["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1), (E,))
    return p


def test_router_groups_bias_and_weights():
    """Against the reference, and by hand: a group's score is the sum of
    its two largest biased scores, two of four groups stay, the four
    largest biased scores among them are chosen, and the weights are the
    UNBIASED scores of the chosen, normalised and times 2.5."""
    p = _moe()
    x = jax.random.normal(jax.random.PRNGKey(5), (40, D))
    w, e = routed_experts.route(x, p["router"], K, bias=p["bias"],
                                groups=(4, 2, 2.5))
    w_ref, e_ref, _, _ = ref.route(x, p, _cfg())
    np.testing.assert_array_equal(e, e_ref)
    np.testing.assert_allclose(w, w_ref, rtol=1e-6)
    s = np.asarray(jax.nn.sigmoid(x @ p["router"]), np.float64)
    biased = s + np.asarray(p["bias"], np.float64)
    for n in range(40):
        groups = biased[n].reshape(4, 4)
        score = np.sort(groups, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-score)[:2]
        assert set(np.asarray(e[n]) // 4) <= set(kept)
        masked = np.where(np.isin(np.arange(E) // 4, kept), biased[n],
                          -np.inf)
        assert set(np.argsort(-masked)[:K]) == set(np.asarray(e[n]))
        np.testing.assert_allclose(
            w[n], 2.5 * s[n][e[n]] / s[n][e[n]].sum(), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    # the bias moved some choice, and no weight
    _, e_plain = routed_experts.route(x, p["router"], K, groups=(4, 2, 2.5))
    assert (np.asarray(e_plain) != np.asarray(e)).any()


def test_router_ties_go_to_the_lower_index():
    """A zero router scores every expert 0.5: groups 0 and 1 stay and the
    four lowest indices are chosen, weights 2.5 / 4 each."""
    x = jnp.ones((3, D))
    w, e = routed_experts.route(x, jnp.zeros((D, E)), K, groups=(4, 2, 2.5))
    np.testing.assert_array_equal(e, np.tile(np.arange(K), (3, 1)))
    np.testing.assert_allclose(w, 2.5 / K)


# ---- (f) the shares add up to the uncut layer -------------------------------
@pytest.mark.parametrize("rows", [24, 40])
def test_the_shares_add_up_to_the_uncut_layer(rows, monkeypatch):
    """Four chips hold four experts each. Their routed parts, and the
    shared expert counted once, are the uncut reference layer; in the dense
    form (24 rows) and in the sorted one (40 rows, past a lowered
    threshold)."""
    monkeypatch.setattr(routed_experts, "DENSE_MAX_TOKENS", 32)
    p = _moe(2)
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, D))
    kw = dict(experts_per_token=K, norm_topk_prob=True, groups=(4, 2, 2.5))
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
        # a share is the reference's share
        want, _ = ref.expert_layer(x, {**mine, "shared": p["shared"]},
                                   _cfg(first=chip * HELD))
        np.testing.assert_allclose(with_shared, want, atol=TOL)
    assert pairs == rows * K                  # every pair landed on one chip
    uncut, _ = ref.expert_layer(x, p, _cfg(held=None))
    np.testing.assert_allclose(total + shared, uncut, atol=TOL)


# ---- (g) a reused slot ------------------------------------------------------
def test_a_reused_slot_gives_the_fresh_servers_tokens():
    """One slot, three requests one after another: each inherits the slot
    the last one left (its recurrent state, tails and latent rows) and
    gives the tokens it gives alone in a fresh server."""
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
    """A finished request's slot rides along in the next steps: its
    recurrent state and tail stay as its last step left them."""
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


# ---- (h) sizing -------------------------------------------------------------
@pytest.mark.parametrize("mixers", [MIXERS, ("attn", "kda", "mla", "kda")])
def test_pool_bytes_count_every_kind(mixers):
    lm = _lm(mixers=mixers)
    slots, t = 3, 40
    cache = SlotKVCache(lm, slots, t, "bfloat16")
    n_kda, n_attn = mixers.count("kda"), mixers.count("attn")
    want = {"kv": 2 * n_attn * slots * t * H * (D // H) * 2,
            "latent": slots * t * 128 * 2,      # 40 numbers in a 128-lane row
            "recurrent": n_kda * slots * H * DK * DK * 4,
            "conv": n_kda * slots * 3 * 3 * H * DK * 2}
    assert cache.nbytes_by_kind == want
    assert cache.nbytes == sum(want.values()) \
        == kv_pool_nbytes(lm, slots, t, "bfloat16")
    assert cache.per_slot_nbytes == sum(want.values()) // slots
    assert max_slots_in_budget(lm, t, 10 * cache.per_slot_nbytes,
                               "bfloat16") == 10
    assert set(cache.state) == ({"latent", "kda", "conv"}
                                | ({"k", "v"} if n_attn else set()))


def test_stats_report_state_bytes_and_the_share():
    lm = _lm()
    server, _ = _served(lm, [(5, 9), (16, 5), (37, 20)],
                        record_routing=True)
    st = server.stats()
    assert st["state_bytes"] == server.engine.cache.nbytes_by_kind
    assert st["state_bytes"]["kv"] == 0
    assert st["kv_pool_bytes"] == sum(st["state_bytes"].values())
    assert np.asarray(st["moe_expert_load"]).shape == (3, HELD)
    # k x held / E = 1 pair a token and layer lands here in expectation
    assert 0.5 < st["moe_pairs_here_per_token"] < 1.5
    assert 0 < st["moe_experts_touched_per_step"] <= 3 * HELD
    assert 1.0 <= st["live_slots_per_step"] <= 3.0


# ---- the reached form of the experts (pallas/reached_experts.py) ------------
def _lane_wide(policy):
    """The small model with hidden and expert widths of one lane tile,
    which the kernel's blocks need: the decode step of 3 slots and the
    prefill rungs of 16 to 64 rows are all under ``REACHED_MAX_ROWS``."""
    return _lm(policy, d_model=128, d_ff=128)


@pytest.mark.parametrize("policy", ["float32", "bf16"])
def test_served_tokens_are_the_same_in_the_reached_form(policy, monkeypatch):
    lengths = [(5, 9), (16, 5), (37, 20), (9, 12)]
    _, want = _served(_lane_wide(policy), lengths)
    monkeypatch.setattr(routed_experts, "_kernel_backend",
                        lambda: "interpret")
    server, got = _served(_lane_wide(policy), lengths)
    assert [r.tokens for r in got] == [r.tokens for r in want]
    st = server.stats()
    assert st["moe_experts_read_per_step"] == \
        st["moe_experts_touched_per_step"] < 3 * HELD


def test_experts_read_says_which_form_a_program_took(monkeypatch):
    """``experts_read`` on the span and in ``stats()``: the cells touched
    where the reached form was traced (with the kernel allowed, the traces
    of no more rows than the bound: here the decode step), layers x held
    where not (the prefill rungs above a bound of 8 rows, booked on the span
    that read the prompt's first token; every program on the CPU default)."""
    from deeplearning4j_tpu.monitor.trace import tracer

    def spans(server):
        return [s for s in tracer().spans()
                if "experts_read" in s.attrs
                and s.name in ("serve.decode", "serve.first_token")]

    lengths = [(5, 9), (16, 5), (20, 7)]
    tracer().clear()
    server, _ = _served(_lane_wide("float32"), lengths)
    assert spans(server) and all(
        s.attrs["experts_read"] == 3 * HELD for s in spans(server))
    assert server.stats()["moe_experts_read_per_step"] == 3 * HELD
    assert server.stats()["moe_experts_touched_per_step"] < 3 * HELD
    tracer().clear()
    monkeypatch.setattr(routed_experts, "_kernel_backend",
                        lambda: "interpret")
    monkeypatch.setattr(routed_experts, "REACHED_MAX_ROWS", 8)
    server, _ = _served(_lane_wide("float32"), lengths)
    by_name = {name: [s for s in spans(server) if s.name == name]
               for name in ("serve.decode", "serve.first_token")}
    assert by_name["serve.decode"] and by_name["serve.first_token"]
    assert all(s.attrs["experts_read"] == s.attrs["experts_touched"]
               for s in by_name["serve.decode"])
    assert all(s.attrs["experts_read"] == 3 * HELD
               for s in by_name["serve.first_token"])
    st = server.stats()
    assert st["moe_experts_read_per_step"] == \
        st["moe_experts_touched_per_step"]


# ---- the mixed stack and the paths that refuse ------------------------------
def test_an_attn_layer_among_the_others_serves():
    """A K/V pool beside latent rows and recurrent state in one cache: the
    program against itself (``forward``), since the reference has no
    'attn' layer."""
    lm = _lm(mixers=("attn", "kda", "mla", "kda"))
    server = DecodeServer(lm, slots=2, max_len=64, buckets=(16, 32))
    reqs = [server.submit(_tokens(n, seed=n), 8) for n in (5, 20, 13)]
    server.drain()
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        logits = lm.forward(lm.params, jnp.asarray(seq[:-1])[None])[0]
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(logits[-8:], -1)), r.tokens)


def test_forward_and_loss_differentiate():
    lm = _lm()
    toks = jnp.asarray(np.stack([_tokens(70), _tokens(70, seed=1)]))
    loss, grads = jax.value_and_grad(lm.loss)(lm.params, toks)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat)
    assert float(jnp.abs(grads["blocks"][1]["kda"]["a_log"]).max()) > 0
    assert float(jnp.abs(grads["blocks"][1]["moe"]["shared"]["w_up"]
                         ).max()) > 0


def test_get_config_rebuilds_the_model():
    lm = _lm()
    again = TransformerLM(**lm.get_config())
    shapes = jax.eval_shape(lambda: again.init().params)
    assert jax.tree_util.tree_map(lambda a: a.shape, shapes) \
        == jax.tree_util.tree_map(lambda a: a.shape, lm.params)
    assert again.mixers == MIXERS and again.experts_held == HELD
    specs = lm.param_specs()
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, lm.params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, specs, is_leaf=lambda a: not isinstance(
                a, (dict, list))))


@pytest.mark.parametrize("what", ["generate", "beam", "handoff",
                                  "scan_layers"])
def test_paths_without_the_new_state_refuse_the_model(what):
    """Every serving path that carries K/V only names what it lacks
    instead of decoding garbage."""
    lm = _lm()
    prompt = _tokens(5)[None]
    if what == "generate":
        with pytest.raises(NotImplementedError, match="recurrent state"):
            lm.generate(prompt, 3)
    elif what == "beam":
        with pytest.raises(NotImplementedError, match="latent"):
            lm.generate_beam(prompt, 3, beam_size=2)
    elif what == "handoff":
        server = DecodeServer(lm, slots=1, max_len=32, buckets=(16,))
        with pytest.raises(ValueError, match="hand-off"):
            handoff.export_slot(server.engine, 0)
    else:
        cfg = dict(lm.get_config(), scan_layers=True)
        with pytest.raises(ValueError, match="scan_layers"):
            TransformerLM(**cfg).init().forward(lm.params,
                                                jnp.asarray(prompt))
