"""OLMoE's block through ``TransformerLM`` and ``DecodeServer`` against the
plain reference (``benchmarks/lib/reference_olmoe.py``), at a small size with
every ratio of the published model kept: hidden 64, 4 heads of 16 (as many kv
heads), 8 experts of width 32, 2 per token, 2 layers, vocabulary 256; RMSNorm,
QK-norm, untied head, RoPE. float32 policy unless a test says otherwise.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import reference_olmoe as ref  # noqa: E402
from deeplearning4j_tpu.models import routed_experts  # noqa: E402
from deeplearning4j_tpu.models.transformer import TransformerLM  # noqa: E402
from deeplearning4j_tpu.serving import DecodeServer  # noqa: E402
from deeplearning4j_tpu.serving import engine as eng  # noqa: E402

V, D, H, F, E, K, L = 256, 64, 4, 32, 8, 2, 2
# float32 on both sides: the program and the reference differ in the order
# of their sums only (a batched matmul over experts against a loop, a
# blocked softmax), which moves a logit by a few 1e-6 of the largest; 1e-4
# leaves two orders of room and is three below what bf16 anywhere would
# cost (2^-8)
REL = 1e-4


@pytest.fixture(params=["dense", "sorted"])
def form(request, monkeypatch):
    """Both forms of the routed FFN are held to the reference: at these
    sizes it runs every expert on every token; "sorted" lowers the token
    count from which it sorts its pairs instead, so the same 24 tokens take
    the grouped matmuls a long prompt takes."""
    if request.param == "sorted":
        monkeypatch.setattr(routed_experts, "DENSE_MAX_TOKENS", 8)
    return request.param


def _cfg(**over):
    return {"num_attention_heads": H, "num_key_value_heads": H,
            "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
            "num_experts_per_tok": K, "norm_topk_prob": False, **over}


def _lm(policy="float32", **over):
    kw = dict(vocab_size=V, d_model=D, num_heads=H, num_layers=L, d_ff=F,
              max_len=64, pos_encoding="rope", dtype_policy=policy,
              attn_impl="xla", norm="rmsnorm", qk_norm=True, num_experts=E,
              experts_per_token=K, tie_embeddings=False, seed=3)
    kw.update(over)
    lm = TransformerLM(**kw).init()
    # unit gains would hide a norm that forgot its weight
    leaves, treedef = jax.tree_util.tree_flatten(lm.params)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    lm.params = jax.tree_util.tree_unflatten(treedef, [
        x * (1 + 0.2 * jax.random.normal(k, x.shape, x.dtype))
        if x.ndim == 1 else x for x, k in zip(leaves, keys)])
    return lm


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, V, n).astype(np.int32)


def _routing(lm, seq):
    """``(experts, weights)``, each [L, t, k], of a plain forward."""
    info = []
    lm.forward(lm.params, jnp.asarray(seq)[None], moe_info=info)
    return tuple(jnp.stack([i[name] for i in info])
                 for name in ("experts", "weights"))


def _close(got, want, rel=REL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


# ---- (a) forward and routing ------------------------------------------------
def test_forward_and_routing_match_the_reference(form, t=24):
    lm, seq = _lm(), _tokens(t)
    logits = lm.forward(lm.params, jnp.asarray(seq)[None])[0]
    want, routes = ref.forward_tail(lm.params, seq, _cfg(), t)
    _close(logits, want)
    experts, weights = _routing(lm, seq)
    for li, (w, e, lead, _) in enumerate(routes):
        np.testing.assert_array_equal(np.asarray(experts[li]), e)
        _close(weights[li], w)
    # told which experts to use, the reference says how admissible they
    # were: its own are (shortfall 0, the same logits); an expert from
    # outside its top k in place of its last falls short, and is used
    again, same = ref.forward_tail(lm.params, seq, _cfg(), t,
                                   chosen=experts)
    assert all(float(r[3].max()) == 0.0 for r in same)
    _close(again, want, rel=1e-6)
    outside = jnp.argmin(jnp.any(
        experts[:, :, :, None] == jnp.arange(E), axis=2), axis=-1)
    swapped = experts.at[:, :, -1].set(outside)
    _, other = ref.forward_tail(lm.params, seq, _cfg(), t, chosen=swapped)
    np.testing.assert_array_equal(np.asarray(other[0][1]), swapped[0])
    assert float(other[0][3].min()) > 0.0
    assert np.all(np.asarray(other[0][3]) >= np.asarray(routes[0][2]) - 1e-6)


# ---- (b) loss and every gradient --------------------------------------------
def test_loss_and_gradients_match_the_reference(form, t=24):
    lm, seq = _lm(), _tokens(t, seed=1)
    loss, grads = jax.value_and_grad(lm.loss)(lm.params,
                                              jnp.asarray(seq)[None])
    want, want_grads = jax.value_and_grad(
        lambda p: ref.mean_nll(p, seq, _cfg()))(lm.params)
    assert abs(float(loss) - float(want)) <= REL * float(want)
    got, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(got, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.abs(w).max()) > 0, path     # no leaf left out
        _close(g, w)


def test_fit_batch_trains_the_routed_block():
    lm = _lm(lr=3e-3)
    batch = np.stack([_tokens(24, seed=s) for s in range(4)])
    losses = [lm.fit_batch(batch) for _ in range(8)]
    assert losses[-1] < losses[0] and np.all(np.isfinite(losses))


# ---- (c) bucket-padded prefill, then decode through the pool ----------------
def _serve_logits(lm, engine, seqs, prompt_lens, slots, steps):
    """Prefill ``seqs[i][:prompt_lens[i]]`` into ``slots[i]``, then feed the
    sequences' own next tokens through the decode step: the logits of each
    step, [steps, S, V]."""
    for seq, n, slot in zip(seqs, prompt_lens, slots):
        engine.prefill(seq[:n], slot, jax.random.PRNGKey(0))
    step = jax.jit(functools.partial(eng._decode_step_body, lm,
                                     pool_kernel=False))
    pos = np.zeros(engine.slots, np.int32)
    pos[slots] = prompt_lens
    live = np.zeros(engine.slots, bool)
    live[slots] = True
    state, out = engine.cache.state, []
    for i in range(steps):
        tok = np.zeros(engine.slots, np.int32)
        tok[slots] = [seq[n + i] for seq, n in zip(seqs, prompt_lens)]
        logits, state = step(lm.params, state, jnp.asarray(tok),
                             jnp.asarray(pos + i * live), live=live)
        out.append(np.asarray(logits))
    return np.stack(out)


def test_prefill_then_decode_through_the_pool_matches_the_reference():
    lm = _lm()
    engine = eng.DecodeEngine(lm, 3, max_len=64, buckets=(16, 32, 64))
    lens, slots, steps = [5, 17], [0, 2], 6       # 11 and 15 pad positions
    seqs = [_tokens(n + steps, seed=n) for n in lens]
    got = _serve_logits(lm, engine, seqs, lens, slots, steps)
    for seq, n, slot in zip(seqs, lens, slots):
        want = ref.tail_logits(lm.params, seq, _cfg(), steps)
        _close(got[:, slot], want)


# ---- (d) a row depends on that row alone ------------------------------------
def test_routed_ffn_rows_are_independent_bit_for_bit(form, n=24):
    """The dropless guarantee at its source: a token's output is the same
    bits whatever the other rows hold, whichever of them are live, and
    however many of them choose the same experts."""
    lm = _lm()
    moe = lm.params["blocks"][0]["moe"]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    run = jax.jit(functools.partial(
        routed_experts.routed_ffn, experts_per_token=K))
    base, _ = run(x, moe)
    others = x.at[8:].set(jnp.asarray(rng.normal(size=(n - 8, D)),
                                      jnp.float32))
    crowd = x.at[8:].set(x[0])             # every other row follows row 0
    live = jnp.arange(n) < 8
    for variant, mask in ((others, None), (crowd, None), (x, live)):
        y, info = run(variant, moe, live=mask)
        np.testing.assert_array_equal(np.asarray(y[:8]),
                                      np.asarray(base[:8]))
    assert int(info["load"].sum()) == 8 * K      # dead rows count nowhere
    assert not np.asarray(y[8:]).any()


def test_served_logits_do_not_depend_on_pad_length_or_neighbours():
    """The same request alone in the smallest bucket that holds it, beside
    two other requests, and in a bucket four times as long. Its decode
    logits are the same bits whatever the other slots hold and route to.
    Across buckets XLA:CPU blocks its matmuls by shape and so reorders a
    row's own sums (2e-7 of the largest logit read): held to 1e-6, where
    a token lost to a capacity limit would move a logit by per cents."""
    lm = _lm()
    seq, n, steps = _tokens(20, seed=9), 14, 6
    mates = [_tokens(30, seed=21), _tokens(12, seed=22)]

    def served(buckets, neighbours):
        engine = eng.DecodeEngine(lm, 3, max_len=64, buckets=buckets)
        seqs = [seq] + neighbours
        lens = [n] + [len(m) - steps for m in neighbours]
        return _serve_logits(lm, engine, seqs, lens,
                             list(range(len(seqs))), steps)[:, 0]

    alone = served((16, 64), [])
    np.testing.assert_array_equal(served((16, 64), mates), alone)
    _close(served((64,), []), alone, rel=1e-6)


# ---- (e) renormalised weights; one expert takes every token -----------------
def test_norm_topk_prob_matches_the_reference(form, t=24):
    lm, seq = _lm(norm_topk_prob=True), _tokens(t, seed=2)
    want, routes = ref.forward_tail(lm.params, seq,
                                    _cfg(norm_topk_prob=True), t)
    _close(lm.forward(lm.params, jnp.asarray(seq)[None])[0], want)
    _, weights = _routing(lm, seq)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    _close(weights[0], routes[0][0])


def test_a_crowded_expert_drops_no_token(form, n=24):
    """Every token's first choice is expert 0: with 8 experts and 2 per
    token, GShard's capacity of 1.25 x n x 2 / 8 would drop two thirds of
    them. Here each is served, as the reference's loop serves it."""
    moe = dict(_lm().params["blocks"][0]["moe"])
    moe["router"] = moe["router"].at[0, 0].set(10.0)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(n, D)),
                    jnp.float32).at[:, 0].set(3.0)
    y, info = routed_experts.routed_ffn(x, moe, experts_per_token=K)
    assert int(info["load"][0]) == n > 1.25 * n * K / E
    w, e, _, _ = ref._route(x, moe["router"], _cfg())
    np.testing.assert_array_equal(np.asarray(info["experts"]), e)
    _close(y, ref._experts(x, w, e, moe))


# ---- (f) today's arguments give today's tree --------------------------------
def test_default_arguments_keep_the_starcoder2_tree():
    from benchmarks.drivers import _lm_common as common

    cfg = {"vocab_size": V, "hidden_size": D, "num_attention_heads": H,
           "num_hidden_layers": L, "intermediate_size": 128,
           "num_key_value_heads": 2, "sliding_window": 16}
    lm = common.build_lm(cfg, policy="bf16", seed=0, max_len=64)
    got = jax.eval_shape(
        lambda: TransformerLM(**lm.get_config()).init().params)
    shapes = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), got)
    norm = {"g": ((D,), "float32"), "b": ((D,), "float32")}
    block = {"ln1": norm, "ln2": norm,
             "attn": {"wq": ((D, D), "float32"), "wk": ((D, 32), "float32"),
                      "wv": ((D, 32), "float32"), "wo": ((D, D), "float32")},
             "mlp": {"w1": ((D, 128), "float32"), "b1": ((128,), "float32"),
                     "w2": ((128, D), "float32"), "b2": ((D,), "float32")}}
    assert shapes == {"embed": ((V, D), "float32"), "ln_f": norm,
                      "blocks": [block] * L}
    # and the benchmark's own check of it, on its own builder's tree
    common._check_tree(lm, "params", jax.eval_shape(
        common._init_fn(lm), jax.random.PRNGKey(0)))


def test_get_config_round_trips_the_block_description():
    lm = _lm(norm_topk_prob=True)
    twin = TransformerLM(**lm.get_config())
    assert (twin.norm, twin.qk_norm, twin.num_experts,
            twin.experts_per_token, twin.norm_topk_prob,
            twin.tie_embeddings) == ("rmsnorm", True, E, K, True, False)
    with pytest.raises(ValueError):
        TransformerLM(V, num_experts=4, experts_per_token=5)
    with pytest.raises(ValueError):
        TransformerLM(V, norm="batchnorm")


# ---- (g) the router is float32 under a bf16 policy --------------------------
def test_router_is_float32_under_the_bf16_policy():
    """Experts 0 and 1 get logits 4.0 and 4.001 from a token that bf16
    holds exactly: bf16 logits (spacing 2^-5 at 4) or a bf16 softmax (the
    probabilities differ by 0.1 %, under 2^-8) would tie and take expert 0;
    float32 takes expert 1."""
    router = jnp.zeros((D, E), jnp.float32).at[0, :2].set(
        jnp.asarray([4.0, 4.001]))
    x = jnp.zeros((3, D), jnp.bfloat16).at[:, 0].set(1.0)
    weights, experts = routed_experts.route(x, router, K)
    assert weights.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(experts), [[1, 0]] * 3)
    assert np.all(np.asarray(weights[:, 0] > weights[:, 1]))
    # and through the block of a bf16 model
    lm = _lm("bf16")
    moe = dict(lm.params["blocks"][0]["moe"], router=router)
    _, info = routed_experts.routed_ffn(
        x, moe, experts_per_token=K, cast=lm.policy.cast_compute)
    np.testing.assert_array_equal(np.asarray(info["experts"]), [[1, 0]] * 3)


def test_bf16_policy_stays_near_the_reference():
    # bf16 activations and weights: a logit moves by about 2^-8 of the
    # largest per matmul on its path; 3 % is what the benchmark's near-tie
    # rule allows and ten times what float32 would need
    lm, seq = _lm("bf16"), _tokens(24, seed=4)
    logits = lm.forward(lm.params, jnp.asarray(seq)[None])[0]
    _close(logits, ref.tail_logits(lm.params, seq, _cfg(), 24), rel=0.03)


# ---- (h) sharding ------------------------------------------------------------
def _mesh():
    from deeplearning4j_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=1, model=2), devices=jax.devices()[:2])


def test_param_specs_cover_every_new_leaf():
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.parallel.sharding_registry import (
        ShardingRegistry)

    lm = _lm()
    specs = lm.param_specs(model_axis_size=2)
    flat, treedef = jax.tree_util.tree_flatten(lm.params)
    flat_specs = treedef.flatten_up_to(specs)      # raises on a missing leaf
    assert len(flat_specs) == len(flat)
    assert all(isinstance(s, P) for s in flat_specs)
    moe = specs["blocks"][0]["moe"]
    assert moe["router"] == P() and moe["w_gate"] == P(None, None, "model")
    assert moe["w_down"] == P(None, "model", None)
    assert specs["head"] == specs["embed"]
    reg = ShardingRegistry.for_transformer(lm, _mesh())  # no UnmappedLeafError
    assert len(reg.leaf_specs(lm.params)) == len(flat)


def test_sharded_server_emits_the_unsharded_servers_tokens():
    prompts = [_tokens(n, seed=n) for n in (5, 17, 30)]

    def serve(mesh):
        server = DecodeServer(_lm(), slots=2, max_len=64,
                              buckets=(16, 32, 64), mesh=mesh)
        reqs = [server.submit(p, 8) for p in prompts]
        server.drain()
        return [r.tokens for r in reqs], server.stats()

    one, stats = serve(None)
    two, stats2 = serve(_mesh())
    assert one == two
    assert stats["moe_expert_load"] == stats2["moe_expert_load"]


# ---- the load the server books ----------------------------------------------
def test_server_books_the_expert_load_of_live_rows_only():
    from deeplearning4j_tpu.monitor import metrics, tracer

    metrics().reset()
    tracer().clear()
    server = DecodeServer(_lm(), slots=4, max_len=64, buckets=(16, 32, 64))
    new = 6
    prompts = [_tokens(n, seed=n) for n in (5, 17)]
    for p in prompts:
        server.submit(p, new)
    server.drain()
    # each prompt token and each decoded token but the last, K pairs a layer
    tokens = sum(len(p) + new - 1 for p in prompts)
    load = np.asarray(server.stats()["moe_expert_load"])
    assert load.shape == (L, E) and (load.sum(axis=1) == tokens * K).all()
    assert metrics().counter("serve_moe_routed_pairs_total").value() \
        == tokens * K * L
    assert 1 / E <= metrics().gauge("serve_moe_max_expert_share").value() <= 1
    # on the span that READ the routing: every prompt's first token, and
    # every decode span but the first, which dispatched a block and had
    # none to read
    spans = [s for s in tracer().spans()
             if s.name in ("serve.decode", "serve.first_token")]
    touched = [s.attrs["experts_touched"] for s in spans
               if "experts_touched" in s.attrs]
    assert len(touched) == len(prompts) + server.steps == len(spans) - 1
    assert all(1 <= t <= L * E for t in touched)
    # off the TPU every program is the dense or the sorted form, which
    # fetch every expert whatever was touched
    assert [s.attrs["experts_read"] for s in spans
            if "experts_read" in s.attrs] == [L * E] * len(touched)
    assert server.stats()["moe_experts_read_per_step"] == L * E
    # a dense model books none of it
    dense = DecodeServer(TransformerLM(V, d_model=D, num_heads=H,
                                       num_layers=1, max_len=64).init(),
                         slots=2, max_len=64)
    dense.submit(prompts[0], 2)
    dense.drain()
    assert "moe_expert_load" not in dense.stats()


# ---- the routing the server records -----------------------------------------
def test_server_records_the_routing_that_served_each_position():
    """``record_routing``: the experts and weights a request keeps are those
    of the prefill and decode programs that wrote its keys and emitted its
    tokens, one row a position. Handed to the reference (``chosen=``) they
    are its own top k (float32 on both sides: no near-tie flips), carry its
    probabilities, and give logits whose argmax is each served token."""
    server = DecodeServer(_lm(), slots=3, max_len=64, buckets=(16, 32, 64),
                          record_routing=True)
    new = 7
    reqs = [server.submit(_tokens(n, seed=n), new) for n in (5, 17, 30, 9)]
    server.drain()
    for r in reqs:
        seq = r.output[:-1]
        experts, weights = (np.concatenate(x, axis=1)
                            for x in zip(*r.routing))
        assert experts.shape == weights.shape == (L, len(seq), K)
        logits, routes = ref.forward_tail(server.model.params, seq, _cfg(),
                                          new, chosen=experts)
        for li, (w, e, _, shortfall) in enumerate(routes):
            assert float(shortfall.max()) == 0.0
            _close(weights[li], w)
        np.testing.assert_array_equal(np.argmax(logits, -1), r.tokens)
    # a server that does not record keeps nothing
    plain = DecodeServer(_lm(), slots=2, max_len=64, buckets=(16, 32, 64))
    req = plain.submit(_tokens(5, seed=5), 3)
    plain.drain()
    assert req.routing is None and req.tokens == reqs[0].tokens[:3]


@pytest.mark.parametrize("why, make", [
    ("dense model", lambda: dict(model=TransformerLM(
        V, d_model=D, num_heads=H, num_layers=1, max_len=64).init())),
])
def test_record_routing_refuses_what_it_cannot_record(why, make):
    kw = make()
    with pytest.raises(ValueError, match="record_routing"):
        DecodeServer(kw.pop("model"), slots=2, max_len=64,
                     record_routing=True, **kw)
