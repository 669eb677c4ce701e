"""GigaChat3.1-702B-A36B's language model (``deepseek_v3``: latent attention
with a compressed query whose rotary part is scaled by YaRN, group-limited
sigmoid routing with a shared expert, one chip's share of the experts, and a
multi-token-prediction module that the server drafts from) through
``TransformerLM`` and ``DecodeServer`` against the plain reference
(``benchmarks/lib/reference_gigachat_mtp.py``), at a small size with the
published model's proportions: hidden 64, 4 heads, a compressed query of 24,
a latent of 32 with 8 rotary dimensions, value heads (24) wider than the
no-position part of the keys (16), 64 experts of width 32 in 8 groups of
which 4 are kept, 4 a token, 8 held here (group 0), a shared expert, a leading
dense SwiGLU layer, vocabulary 128, YaRN with a factor of 64 from an original
context of 16 (so the tests' positions lie on both sides of it). float32
policy; ``docs/gigachat_mtp.md`` has the equations.
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

from benchmarks.lib import reference_gigachat_mtp as ref  # noqa: E402
from deeplearning4j_tpu.models import mla, routed_experts  # noqa: E402
from deeplearning4j_tpu.models import transformer  # noqa: E402
from deeplearning4j_tpu.models.transformer import TransformerLM  # noqa: E402
from deeplearning4j_tpu.monitor import trace as program_trace  # noqa: E402
from deeplearning4j_tpu.serving import (  # noqa: E402
    DecodeServer, SlotKVCache, kv_pool_nbytes)
from deeplearning4j_tpu.serving import engine as eng  # noqa: E402
from deeplearning4j_tpu.serving.fleet import handoff  # noqa: E402

V, D, H, F, E, K, HELD = 128, 64, 4, 32, 64, 4, 8
MLA = {"q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 24, "gate": False}
YARN = {"rope_type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16}
# the published rotary part and scaling (64 dimensions, base 100,000, a
# factor of 64 from 4,096 positions)
PUBLISHED = dict(YARN, original_max_position_embeddings=4096)
# float32 on both sides: the program's absorbed attention over cached rows
# and its batched experts differ from the reference's expanded keys and its
# expert loop in the order of their sums only (ISSUE 35 allows 1e-4)
TOL = 2e-5


def _cfg(first=0, held=HELD):
    share = None if held is None else {"first_expert": first, "held": held}
    return {"num_attention_heads": H, "rms_norm_eps": 1e-6,
            "rope_theta": 1e5, "rope_interleave": True, "rope_scaling": YARN,
            **{k: v for k, v in MLA.items() if k != "gate"},
            "num_experts_per_tok": K, "n_group": 8, "topk_group": 4,
            "routed_scaling_factor": 2.5, "share": share}


def _lm(policy="float32", seed=3, first=0, held=HELD, mtp=True, layers=3,
        **over):
    kw = dict(
        vocab_size=V, d_model=D, num_heads=H, num_layers=layers, d_ff=F,
        max_len=256, pos_encoding="rope", dtype_policy=policy,
        norm="rmsnorm", num_experts=E, experts_per_token=K,
        norm_topk_prob=True, tie_embeddings=False, seed=seed,
        rope_theta=1e5, rope_interleaved=True, norm_eps=1e-6,
        mixers=("mla",) * layers, ffns=("glu",) + ("moe",) * (layers - 1),
        glu_width=96, mla=MLA, rope_scaling=YARN,
        mtp={"loss_weight": 0.3} if mtp else None,
        moe={"n_group": 8, "topk_group": 4, "scale": 2.5, "bias": True,
             "shared_width": F, "first": first, "held": held})
    kw.update(over)
    lm = TransformerLM(**kw).init()
    # ones would hide a norm that forgot its gain; a sharper query makes
    # attention depend on the positions
    blocks = lm.params["blocks"] + (
        [lm.params["mtp"]["block"]] if lm.mtp else [])
    for blk, key in zip(blocks, jax.random.split(
            jax.random.PRNGKey(seed + 99), len(blocks))):
        k = jax.random.split(key, 2)
        p = blk["mla"]
        p["kv_norm"]["g"] = 1 + 0.1 * jax.random.normal(k[0], (32,))
        p["q_norm"]["g"] = 1 + 0.1 * jax.random.normal(k[1], (24,))
        p["wq_b"] = 4.0 * p["wq_b"]
    if lm.mtp:
        k = jax.random.split(jax.random.PRNGKey(seed + 7), 3)
        for name, key in zip(("enorm", "hnorm", "norm"), k):
            lm.params["mtp"][name]["g"] = 1 + 0.1 * jax.random.normal(
                key, (D,))
    return lm


def _plain(lm):
    """The same model without its module, on the same weights: what is
    served a token a step."""
    cfg = dict(lm.get_config(), mtp=None)
    plain = TransformerLM(**cfg)
    plain.params = {k: v for k, v in lm.params.items() if k != "mtp"}
    return plain


def _tokens(n, seed=0, vocab=V):
    return np.random.default_rng(seed).integers(1, vocab, n).astype(np.int32)


@pytest.fixture(autouse=True)
def _highest():
    """float32 matmuls as written on both sides."""
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks small enough for the tests' prompts to span several: 8
    positions a prefill block, 4 queries at a time."""
    monkeypatch.setattr(eng, "PREFILL_BLOCK", 8)
    monkeypatch.setattr(mla, "QUERY_BLOCK", 4)


def _served(lm, lengths, slots=3, buckets=(16, 32, 64), prompts=None, **kw):
    server = DecodeServer(lm, slots=slots, max_len=128, buckets=buckets,
                          **kw)
    prompts = prompts or [_tokens(n, seed=n) for n, _ in lengths]
    reqs = [server.submit(p, k) for p, (_, k) in zip(prompts, lengths)]
    server.drain()
    return server, reqs


def _seq(req):
    return np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])[:-1]


def _rounds(fn):
    """``fn()`` with the ``serve.decode`` spans it left: their attrs."""
    spans = []
    program_trace.add_sink(spans.append)
    try:
        out = fn()
    finally:
        program_trace.remove_sink(spans.append)
    return out, spans


# ---- (a) the forward and the module are the reference ------------------------
@pytest.mark.parametrize("t", [5, 16, 40])
def test_forward_and_module_are_the_reference(t):
    """Logits of the model and of its module over t positions (below and
    beyond YaRN's original context of 16), float32 <= 2e-5: the program's
    latent attention, YaRN, grouped routing and its module against the
    reference's own."""
    lm = _lm()
    toks = _tokens(t)
    want, extra, routes = ref.forward(lm.params, toks, _cfg())
    got = lm.forward(lm.params, jnp.asarray(toks)[None])[0]
    np.testing.assert_allclose(got, want, atol=TOL)
    h = lm._hidden(lm.params, jnp.asarray(toks)[None])
    g = lm._norm(h, lm.params["ln_f"])
    mine = lm.mtp_logits(lm.params, g[:, :-1], jnp.asarray(toks)[None, 1:])
    np.testing.assert_allclose(mine[0], extra[:-1], atol=TOL)
    assert len(routes) == 3         # two expert layers and the module's


def test_the_module_reads_the_hidden_state_and_the_next_token():
    """Zeroing the hidden-state half of the module's input (the benchmark's
    module-off control) or shifting the tokens moves its logits."""
    lm = _lm()
    toks = _tokens(12)
    _, extra, _ = ref.forward(lm.params, toks, _cfg())
    _, off, _ = ref.forward(lm.params, toks, _cfg(), module_off=True)
    assert float(jnp.abs(extra - off)[:-1].max()) > 1e-2
    h = lm._hidden(lm.params, jnp.asarray(toks)[None])
    g = lm._norm(h, lm.params["ln_f"])
    same = lm.mtp_logits(lm.params, g[:, :-1], jnp.asarray(toks)[None, :-1])
    assert float(jnp.abs(same[0] - extra[:-1]).max()) > 1e-2


def test_the_loss_adds_the_modules_term():
    """``loss`` = the model's cross entropy + 0.3 x the module's against the
    tokens two on, as the reference writes it; without a module the loss is
    what it was."""
    lm = _lm()
    toks = _tokens(20)
    want = ref.loss(lm.params, toks, _cfg(), 0.3)
    got = lm.loss(lm.params, jnp.asarray(toks)[None])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    plain = _plain(lm)
    alone = plain.loss(plain.params, jnp.asarray(toks)[None])
    assert float(got) > float(alone) > 0
    logits = plain.forward(plain.params, jnp.asarray(toks)[None])[0, :-1]
    logp = jax.nn.log_softmax(logits, -1)
    np.testing.assert_allclose(
        alone, -jnp.mean(logp[jnp.arange(19), toks[1:]]), rtol=1e-6)


# ---- (b) YaRN ------------------------------------------------------------------
def test_yarn_frequencies_are_the_references():
    """The published rotary part: 32 frequencies, the first nine untouched
    (corr(32) = 8.4: they turn more than 32 times in 4,096 positions), those
    from the 19th on divided by 64 (corr(1) = 18.0), a ramp between; and the
    tests' own scaling."""
    for d, scaling in ((64, PUBLISHED), (8, YARN)):
        got = transformer.rope_frequencies(d, 1e5, scaling)
        np.testing.assert_allclose(
            got, ref.yarn_frequencies(d, 1e5, scaling), rtol=1e-12)
        plain = 1e5 ** (-np.arange(d // 2) / (d // 2))
        np.testing.assert_allclose(
            transformer.rope_frequencies(d, 1e5, None), plain, rtol=1e-12)
    got = transformer.rope_frequencies(64, 1e5, PUBLISHED)
    plain = 1e5 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:9], plain[:9], rtol=1e-12)
    np.testing.assert_allclose(got[19:], plain[19:] / 64, rtol=1e-12)
    ratio = plain / got
    assert np.all(np.diff(ratio) >= 0) and 1 < ratio[9] < ratio[18] < 64


@pytest.mark.parametrize("interleave", [True, False])
def test_rope_under_yarn_is_the_references(interleave):
    """The rotation at positions below and far above the original 4,096, both
    pairings, against the reference's own rotation."""
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 2, 64))
    pos = jnp.asarray([0, 7, 4095, 4096, 100000, 262143])
    got = transformer._rope(x[None], pos, 1e5, interleave, PUBLISHED)[0]
    cfg = {"rope_theta": 1e5, "rope_interleave": interleave,
           "rope_scaling": PUBLISHED}
    np.testing.assert_allclose(got, ref._rope(x, cfg, pos), atol=2e-5)
    bare = transformer._rope(x[None], pos, 1e5, interleave)[0]
    assert float(jnp.abs(bare - got)[4].max()) > 0.1


def test_the_softmax_scale_takes_mscale_squared():
    """``192^-1/2 (0.1 ln 64 + 1)^2`` at the published sizes, in the program
    and in the reference; cos and sin stay unscaled (mscale = mscale_all_dim);
    without scaling the scale is what it was."""
    dims = {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64}
    want = 192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2
    assert abs(want / 192 ** -0.5 - 2.0047) < 1e-4
    lm = _lm()
    assert lm.mla["softmax_mult"] == pytest.approx(
        (0.1 * np.log(64) + 1) ** 2)
    assert mla.softmax_scale({**dims, "softmax_mult": lm.mla[
        "softmax_mult"]}) == pytest.approx(want)
    assert ref.softmax_scale({**dims, "rope_scaling": PUBLISHED}
                             ) == pytest.approx(want)
    assert mla.softmax_scale(dims) == pytest.approx(192 ** -0.5)
    assert transformer.yarn_mscale(64, 1) / transformer.yarn_mscale(
        64, 1) == 1.0
    with pytest.raises(ValueError, match="YaRN"):
        _lm(rope_scaling=dict(YARN, rope_type="linear"))


# ---- (c) prefill in blocks, then rounds ----------------------------------------
def _spy(monkeypatch):
    """The logits of every round's verify forward and of the module's head
    in it, as the programs computed them."""
    seen = {"verify": [], "module": []}
    verify, head = eng._serve_verify_impl, TransformerLM.mtp_head

    def spy_verify(*a, **kw):
        out = verify(*a, **kw)
        jax.debug.callback(
            lambda x: seen["verify"].append(np.asarray(x)), out[0])
        return out

    def spy_head(self, params, h):
        logits = head(self, params, h)
        if logits.ndim == 2:      # a round's: [S, V]
            jax.debug.callback(
                lambda x: seen["module"].append(np.asarray(x)), logits)
        return logits

    monkeypatch.setattr(eng, "_serve_verify_impl", spy_verify)
    monkeypatch.setattr(TransformerLM, "mtp_head", spy_head)
    return seen


def test_round_logits_equal_the_reference(monkeypatch, small_blocks):
    """Logits, not tokens: after n prompt tokens (three prefill blocks and a
    pad tail) the j-th round's verify logits at the cursor are the
    reference's at position n + j, and the module's logits there the
    reference's module at n + j (seeded weights: every round moves one
    position)."""
    lm = _lm()
    seen = _spy(monkeypatch)
    server = DecodeServer(lm, slots=2, max_len=64, buckets=(16, 32))
    req = server.submit(_tokens(21), 8)
    server.drain()
    assert server.stats()["spec_accepted"] == 0
    want, extra, _ = ref.forward(lm.params, _seq(req), _cfg())
    got = np.stack([s[req.slot, 0] for s in seen["verify"][:7]])
    np.testing.assert_allclose(got, np.asarray(want)[-7:], atol=5e-5)
    got = np.stack([s[req.slot] for s in seen["module"][:6]])
    np.testing.assert_allclose(got, np.asarray(extra)[-7:-1], atol=5e-5)


def test_prefill_then_rounds_is_the_reference_forward(small_blocks):
    """Five requests over three slots (a slot is reused, slots freeze while
    others go on): every token is the reference's argmax over the whole
    sequence, every draft a round verified the reference module's, and the
    routing recorded through the round program the reference's own."""
    lm = _lm()
    lengths = [(5, 9), (16, 5), (37, 20), (20, 7), (9, 12)]
    server, reqs = _served(lm, lengths, record_routing=True)
    for r in reqs:
        want, extra, routes = ref.forward(lm.params, _seq(r), _cfg())
        n = len(r.tokens)
        assert r.tokens == np.argmax(np.asarray(want)[-n:], -1).tolist()
        module = np.argmax(np.asarray(extra), -1)
        judged = [(q, d) for q, d in r.drafts if q - 2 < len(module) - 1]
        assert len(judged) >= n - 2
        assert all(d == module[q - 2] for q, d in judged)
        # the served routing (a near-tie may flip) is admissible to the
        # reference, and with it the tokens are still the argmax
        experts, weights = (np.concatenate(x, axis=1)
                            for x in zip(*r.routing))
        assert experts.shape == (3, len(_seq(r)), K)
        again, _, routes = ref.forward(lm.params, _seq(r), _cfg(),
                                       chosen=experts, after=r.tokens[-1])
        assert r.tokens == np.argmax(np.asarray(again)[-n:], -1).tolist()
        for layer, route in enumerate(routes):
            assert float(route[3].max()) < 1e-4
            np.testing.assert_allclose(weights[layer], route[0], rtol=1e-3)


def test_seeded_rounds_are_the_plain_greedy_decode(small_blocks):
    """Seeded weights accept nothing: a round is one token, and the tokens
    are the plain decode step's, slot for slot."""
    lm = _lm()
    lengths = [(5, 9), (16, 5), (37, 20), (20, 7), (9, 12), (3, 1), (8, 2)]
    server, reqs = _served(lm, lengths)
    _, plain = _served(_plain(lm), lengths)
    assert [r.tokens for r in reqs] == [r.tokens for r in plain]
    st = server.stats()
    assert st["speculative"] and st["spec_tokens"] == 1
    assert st["spec_accepted"] == 0 and st["spec_rounds"] == st[
        "spec_emitted"] == sum(k - 1 for _, k in lengths)
    assert st["tokens_per_slot_dispatch"] == 1.0


def test_sampled_rounds_draw_and_keep_their_streams(small_blocks):
    """Temperature > 0 runs ``_accept_round``'s accept / resample rule
    with a one-hot proposal distribution: the same seed gives the same
    tokens whatever else the batch holds, another seed other tokens."""
    lm = _lm()
    def run(others):
        server = DecodeServer(lm, slots=3, max_len=64, buckets=(16,),
                              temperature=0.8, top_k=20)
        req = server.submit(_tokens(9), 10, seed=5)
        more = [server.submit(_tokens(4 + i, seed=i), 6, seed=i)
                for i in range(others)]
        server.drain()
        return req.tokens, [m.tokens for m in more]
    alone, _ = run(0)
    crowded, others = run(2)
    assert alone == crowded and len(alone) == 10
    assert others[0] != others[1]


# ---- (d) a trained module: drafts that are accepted ------------------------------
PERIOD = (3, 9, 4, 11, 6, 2, 13)
SMALL = dict(vocab_size=16, d_model=32, num_layers=2, num_experts=0,
             experts_per_token=0, mixers=("mla",) * 2, ffns=("glu",) * 2,
             glu_width=64, moe=None, d_ff=32, lr=4e-3,
             mla={"q_lora_rank": 16, "kv_lora_rank": 16,
                  "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
                  "v_head_dim": 12, "gate": False})


def _stream(n, phase):
    return np.asarray([PERIOD[(i + phase) % len(PERIOD)] for i in range(n)],
                      np.int32)


def _train():
    """A tiny model trained with the module's loss on a periodic stream (a
    period of 7 over 16 tokens) until its module predicts the token two on."""
    with jax.default_matmul_precision("highest"):
        lm = TransformerLM(**{**dict(
            num_heads=H, max_len=128, pos_encoding="rope",
            dtype_policy="float32", norm="rmsnorm", tie_embeddings=False,
            seed=11, rope_theta=1e5, rope_interleaved=True, norm_eps=1e-6,
            rope_scaling=YARN, mtp={"loss_weight": 0.3}), **SMALL}).init()
        step = lm.make_train_step(donate=False)
        batch = np.stack([_stream(24, p) for p in range(14)])
        for _ in range(160):
            loss = lm.fit_batch(batch, train_step=step)
        assert loss < 0.2
        lm.opt_state = None
        return lm


@pytest.fixture(scope="module")
def trained():
    return _train()


def _serve_counted(lm, prompts, news, slots=2):
    def run():
        server = DecodeServer(lm, slots=slots, max_len=128,
                              buckets=(16, 32))
        reqs = [server.submit(p, k) for p, k in zip(prompts, news)]
        server.drain()
        return server, reqs

    (server, reqs), spans = _rounds(run)
    rounds = [s["attrs"] for s in spans if s["name"] == "serve.decode"
              and "rounds" in s["attrs"]]
    return server, reqs, rounds


def test_a_trained_module_is_accepted_and_changes_no_token(trained,
                                                           small_blocks):
    """On the stream it was trained on most drafts are accepted (two tokens
    a round), and the tokens are the plain greedy decode's: requests that end
    on an accepted pair owing one token, a reused slot, a slot frozen while
    its neighbour goes on."""
    prompts = [_stream(9, 2), _stream(14, 5), _stream(5, 0), _stream(11, 3),
               _stream(7, 6)]
    news = [12, 2, 3, 9, 4]
    server, reqs, rounds = _serve_counted(trained, prompts, news)
    plain = DecodeServer(_plain(trained), slots=2, max_len=128,
                         buckets=(16, 32))
    want = [plain.submit(p, k) for p, k in zip(prompts, news)]
    plain.drain()
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    st = server.stats()
    assert st["spec_accept_rate"] > 0.5
    assert st["spec_emitted"] > 1.5 * st["spec_rounds"]
    assert st["steps"] < plain.stats()["steps"]
    assert sum(r["accepted"] for r in rounds) > 0.5 * sum(
        r["proposed"] for r in rounds)
    # a request owed one token when its round accepted two: the round's
    # second token is dropped by the host, not emitted
    assert st["spec_emitted"] > st["decode_tokens"]


def test_a_request_that_ends_on_an_accepted_draft(trained, small_blocks):
    """Rounds are read one dispatch behind, so the host picks a dispatch's
    slots by what the unread round holds at least, one token. A lone request
    owed four tokens gets them from two rounds of two; the host, which has
    counted 4 - 2 - 1 > 0, dispatches a third, the device has frozen the slot
    and the block comes back with a count of 0: the request retires a step
    later than in the synchronous order (a ``flush()`` after every step), and
    ``empty_dispatches`` counts the round for nothing. Beside a request that
    goes on, the same slot-round is no dispatch of its own."""
    def serve(news, sync, slots=1):
        server = DecodeServer(trained, slots=slots, max_len=128,
                              buckets=(16, 32))
        reqs = [server.submit(_stream(9 + 2 * i, 2 + i), k)
                for i, k in enumerate(news)]
        done_at = {}

        def run():
            step = 0
            while server.busy():
                server.step()
                if sync:
                    server.flush()
                step += 1
                for r in reqs:
                    if r.state == "finished":
                        done_at.setdefault(r.id, step)

        _, spans = _rounds(run)
        blocks = [s["attrs"] for s in spans if s["name"] == "serve.decode"
                  and "rounds" in s["attrs"]]
        return server, reqs, blocks, [done_at[r.id] for r in reqs]

    sync, want, blocks, (then,) = serve([5], True)
    assert [b["emitted"] for b in blocks] == [2, 2]
    assert sync.stats()["empty_dispatches"] == 0
    assert sync.stats()["decode_ahead_share"] == 0.0

    server, reqs, blocks, done = serve([5], False)
    assert reqs[0].tokens == want[0].tokens
    assert done == [then + 1]
    assert [(b["rounds"], b["emitted"]) for b in blocks] == [
        (1, 2), (1, 2), (0, 0)]
    st = server.stats()
    assert (st["steps"], st["empty_dispatches"]) == (3, 1)
    assert (server.slot_dispatches, st["spec_rounds"]) == (3, 2)
    assert st["decode_ahead_share"] == round(2 / 3, 4)
    # cursor: the prompt's 9 and four tokens, whatever the third round read
    assert server.engine.slot_state(0)[0] == server._cursors[0] == 13

    # owed three tokens, the host's count says so: 3 - 2 - 1 = 0
    server, reqs, blocks, _ = serve([4], False)
    assert server.stats()["empty_dispatches"] == 0 and len(blocks) == 2

    # beside a longer request the third round of the first is a row of a
    # block that others fill: one slot-round more, no dispatch more
    server, reqs, blocks, _ = serve([5, 12], False, slots=2)
    _, want, _, _ = serve([5, 12], True, slots=2)
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    st = server.stats()
    assert st["empty_dispatches"] == 0
    assert server.slot_dispatches > st["spec_rounds"]


def test_a_rejection_after_an_acceptance(trained, small_blocks):
    """The trained model with noise on the module's ``M`` (the target is
    untouched): some drafts now miss. A round that rejects yields the
    target's own token and rewinds, the rounds after it accept again, and
    the tokens stay the plain decode's."""
    noisy = TransformerLM(**trained.get_config())
    m = dict(trained.params["mtp"])
    m["proj"] = m["proj"] + 2.0 * jnp.std(m["proj"]) * jax.random.normal(
        jax.random.PRNGKey(1), m["proj"].shape)
    noisy.params = {**trained.params, "mtp": m}
    prompts = [_tokens(7, seed=s, vocab=16) for s in range(3)]
    plain = DecodeServer(_plain(trained), slots=1, max_len=128,
                         buckets=(16, 32))
    seen = []
    for p in prompts:
        _, (r,), rounds = _serve_counted(noisy, [p], [14], slots=1)
        want = plain.submit(p, 14)
        plain.drain()
        assert r.tokens == want.tokens
        seen.append("".join(str(x["accepted"]) for x in rounds))
    assert any("10" in s for s in seen) and any("01" in s for s in seen)


# ---- (e) the share ---------------------------------------------------------------
@pytest.mark.parametrize("rows", [24, 40])
def test_the_shares_add_up_to_the_uncut_layer(rows, monkeypatch):
    """Eight chips hold 8 of the 64 experts each (a group each; the
    published share is a quarter of a group). Their routed parts, and the
    shared expert counted once, are the uncut reference layer; in the dense
    form (24 rows) and in the sorted one (40 rows, past a lowered
    threshold)."""
    monkeypatch.setattr(routed_experts, "DENSE_MAX_TOKENS", 32)
    p = _lm(held=None, mtp=False).params["blocks"][2]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, D))
    kw = dict(experts_per_token=K, norm_topk_prob=True, groups=(8, 4, 2.5))
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


# ---- (f) state, spans, scopes ------------------------------------------------------
def test_pool_bytes_count_the_modules_layer():
    """Four layers of latent rows for three layers and the module, one pool,
    one cursor a slot; the loop state carries the draft."""
    lm = _lm()
    cache = SlotKVCache(lm, 4, 64)
    row = 128 * 4           # 40 numbers in 128 lanes, float32
    assert cache.nbytes_by_kind["latent"] == 4 * 4 * 64 * row
    assert cache.nbytes == kv_pool_nbytes(lm, 4, 64)
    assert len(cache.latent) == 4 and cache.k is None
    assert set(cache.loop) == {"cursors", "tok", "remaining", "keys",
                               "draft"}
    plain = SlotKVCache(_plain(lm), 4, 64)
    assert len(plain.latent) == 3 and "draft" not in plain.loop
    server = DecodeServer(lm, slots=4, max_len=64, buckets=(16,))
    st = server.stats()
    assert st["state_bytes"]["latent"] == cache.nbytes_by_kind["latent"]
    assert server.engine.spec and not hasattr(server.engine, "draft_cache")


def test_spans_and_counters(small_blocks):
    """``serve.decode`` carries ``rounds``, ``proposed``, ``accepted`` and
    ``emitted`` (host bookkeeping of the block read); ``stats()`` their
    totals; ``serve.prefill`` its blocks; the expert load counts the
    module's layer."""
    lm = _lm()
    (server, reqs), spans = _rounds(
        lambda: _served(lm, [(21, 6), (5, 3)], slots=2))
    prefill = [s for s in spans if s["name"] == "serve.prefill"]
    assert sorted(s["attrs"]["blocks"] for s in prefill) == [1, 3]
    decode = [s["attrs"] for s in spans if s["name"] == "serve.decode"
              and "rounds" in s["attrs"]]
    assert all(d["kind"] == "spec" and d["proposed"] == d["rounds"]
               and d["emitted"] == d["rounds"] + d["accepted"]
               for d in decode)
    st = server.stats()
    assert st["spec_rounds"] == sum(d["rounds"] for d in decode) == 7
    assert st["spec_emitted"] == sum(d["emitted"] for d in decode)
    assert np.asarray(st["moe_expert_load"]).shape == (3, HELD)
    assert st["compiles"]["decode"] == 1


def test_the_blocks_of_a_prompt_add_up_the_rows_their_passes_ran(
        monkeypatch, small_blocks):
    """A prompt prefilled in blocks long enough for the sorted experts: each
    block runs whole passes over its own pairs held here, the carry adds
    them up, and the last block's routing hands the sum to the server --
    held to the routing it recorded (four expert layers, the module's among
    them; 8 rows a block, 8 of 64 experts held)."""
    from deeplearning4j_tpu.models import routed_experts

    monkeypatch.setattr(routed_experts, "DENSE_MAX_TOKENS", 4)
    monkeypatch.setattr(routed_experts, "PASS_TILE", 2)
    lm = _lm()
    pass_rows = routed_experts._pass_rows(8, K, HELD, E, False)
    assert pass_rows < 8 * K
    program_trace.tracer().clear()
    server, (req,) = _served(lm, [(21, 3)], buckets=(16, 32),
                             record_routing=True)
    experts = np.asarray(req.routing[0][0])                 # [L, 21, k]
    assert experts.shape[1:] == (21, K)
    here = (experts >= 0) & (experts < HELD)
    blocks = [here[:, i:i + 8].sum(axis=(1, 2)) for i in (0, 8, 16)]
    rows = sum(-(-int(h) // pass_rows) * pass_rows
               for block in blocks for h in block)
    spans = program_trace.tracer().spans()
    # the prompt's routing comes with its first token (ISSUE 48)
    token, = [s for s in spans if s.name == "serve.first_token"]
    passes, = [s for s in spans if s.name == "serve.passes"
               and s.parent_id == token.span_id]
    assert passes.attrs["moe_rows_run"] == rows > 0
    assert passes.attrs["moe_pairs_run"] == int(here.sum()) <= rows
    # the rounds' six rows are past the lowered threshold too
    st = server.stats()
    assert st["moe_rows_run"] >= rows and st["moe_pairs_run"] >= here.sum()


def test_the_scopes_name_the_parts():
    """``mtp.embed``, ``mtp.proj``, the module's block under ``mtp`` and
    ``spec.accept`` reach the round program's HLO, the module's the prefill
    block's too."""
    lm = _lm()
    engine = eng.DecodeEngine(lm, 2, max_len=32, buckets=(16,))
    sample = eng._row_sampler(0.0, None)
    rounds = jax.jit(lambda p, kv, loop: eng._serve_mtp_impl(
        lm, None, True, 1, p, kv, loop)).lower(
            lm.params, engine.cache.state, engine.cache.loop).as_text(
                debug_info=True)
    carry = {name: jnp.full(shape, fill, jnp.dtype(dt)) for name, (
        shape, dt, fill) in eng.prefill_carry_layout(lm, 16).items()}
    prefill = jax.jit(lambda *a: eng._serve_prefill_block_impl(
        lm, sample, *a)).lower(
            lm.params, engine.cache.state, carry, jnp.zeros((1, 16), jnp.int32),
            jnp.int32(9), jnp.int32(0), jax.random.PRNGKey(0),
            jnp.int32(0)).as_text(debug_info=True)
    for text in (rounds, prefill):
        for scope in ("mtp.embed", "mtp.proj", "mtp/mla.proj",
                      "mtp/mla.attend", "mtp/moe.route", "mtp/lm.head",
                      "mla.attend", "kv.write"):
            assert scope in text, scope
    assert "spec.accept" in rounds


# ---- (g) the description and what still refuses --------------------------------------
def test_get_config_rebuilds_the_model():
    lm = _lm()
    again = TransformerLM(**lm.get_config()).init()
    assert again.mtp == {"loss_weight": 0.3}
    assert again.rope_scaling == YARN
    assert again.mla["softmax_mult"] == lm.mla["softmax_mult"]
    assert jax.tree_util.tree_structure(again.params) \
        == jax.tree_util.tree_structure(lm.params)
    specs = lm.param_specs(model_axis_size=1)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, lm.params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda x: 0, specs, is_leaf=lambda x: isinstance(x, tuple)))
    assert lm.n_layers("mla") == 4 and lm.n_layers("moe") == 3
    assert _plain(lm).n_layers("mla") == 3


@pytest.mark.parametrize("bad", ["learned", "last_attn", "indexer"])
def test_a_module_that_cannot_be_built_is_refused(bad):
    kw = dict(vocab_size=V, d_model=D, num_heads=H, num_layers=2, d_ff=F,
              pos_encoding="rope", norm="rmsnorm", mixers=("mla", "mla"),
              ffns=("glu", "glu"), glu_width=96, mla=MLA,
              mtp={"loss_weight": 0.3})
    kw.update({"learned": {"pos_encoding": "learned"},
               "last_attn": {"mixers": ("mla", "attn")},
               "indexer": {"indexers": ("full", "full"),
                           "dsa": {"n_heads": 4, "head_dim": 16, "topk": 8,
                                   "rope_dim": 8}}}[bad])
    with pytest.raises(ValueError, match="mtp="):
        TransformerLM(**kw)


@pytest.mark.parametrize("what", ["generate", "beam", "mesh", "handoff",
                                  "scan_layers", "mixed", "kda"])
def test_paths_without_the_new_state_refuse_the_model(what):
    """What PR 31 listed for a stack of 'mla' layers still refuses by name,
    and so does speculative decoding where it is not written."""
    lm = _lm()
    prompt = _tokens(5)[None]
    if what == "generate":
        with pytest.raises(NotImplementedError, match="latent"):
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
    elif what == "scan_layers":
        cfg = dict(lm.get_config(), scan_layers=True)
        with pytest.raises(ValueError, match="scan_layers"):
            TransformerLM(**cfg).init().forward(lm.params,
                                                jnp.asarray(prompt))
    elif what == "mixed":
        mixed = TransformerLM(**dict(lm.get_config(),
                                     mixers=("attn", "mla", "mla")))
        with pytest.raises(NotImplementedError, match="stack of 'mla'"):
            DecodeServer(mixed, slots=1, max_len=32)
    else:
        hybrid = TransformerLM(**dict(
            lm.get_config(), mixers=("kda", "mla", "mla"),
            kda={"head_dim": 16, "conv": 4, "lower": -5.0}))
        with pytest.raises(ValueError, match="speculative"):
            DecodeServer(hybrid, slots=1, max_len=32)
