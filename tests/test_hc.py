"""Manifold-constrained hyper-connections (``models/hc.py``,
``TransformerLM(hc=)``) against the plain reference's written sweeps
(``benchmarks/lib/reference_glm53.py``), and ``hc=None`` against the block as
it was: the plain residual, bit for bit."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import reference_glm53 as ref  # noqa: E402
from deeplearning4j_tpu.models import hc  # noqa: E402
from deeplearning4j_tpu.models.transformer import TransformerLM  # noqa: E402

N, D = 4, 32
DIMS = {"streams": N, "sinkhorn_iters": 20, "eps": 1e-6}
CFG = {"rms_norm_eps": 1e-5, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6}


def _params(seed, alpha=1.0):
    """Maps far from the identity: alpha of order 1, no large diagonal; a
    projection of standard deviation 0.5 (x~ has unit mean square)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"phi": jax.random.normal(k[0], (N * D, 2 * N + N * N))
            * 0.5 * (N * D) ** -0.5,
            "alpha": jnp.full((3,), alpha) * jnp.array([1.0, 0.8, 1.2]),
            "b": jax.random.normal(k[1], (2 * N + N * N,)) * 0.5}


def _streams(seed, b=2, t=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, N, D))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_maps_equal_the_reference_sweeps(seed):
    x, p = _streams(seed), _params(seed)
    pre, post, res = hc.maps(x, p, dims=DIMS, norm_eps=1e-5)
    for b in range(x.shape[0]):
        with jax.default_matmul_precision("highest"):
            want = ref.hc_maps(x[b], p, CFG)
        np.testing.assert_allclose(pre[:, b].T, want[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(post[:, b].T, want[1], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(jnp.moveaxis(res[:, :, b], 2, 0), want[2],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seed", [0, 3])
def test_res_map_is_doubly_stochastic(seed):
    _, _, res = hc.maps(_streams(seed), _params(seed), dims=DIMS,
                        norm_eps=1e-5)
    assert float(res.min()) > 0
    # the last half-sweep normalises the columns: exact to the epsilon
    np.testing.assert_allclose(res.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-3)
    # and the maps are no identity here: the controls can fail
    assert float(jnp.abs(res - jnp.eye(N)[:, :, None, None]).max()) > 0.3


@pytest.mark.parametrize("iters", [1, 5, 20])
def test_every_sweep_is_computed(iters):
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(7), (N, N, 6)))
    got = hc.sinkhorn(m, iters, 1e-6)
    want = ref.sinkhorn(jnp.moveaxis(m, 2, 0), iters, 1e-6)
    np.testing.assert_allclose(jnp.moveaxis(got, 2, 0), want, rtol=1e-5)
    if iters < 20:      # fewer sweeps is another map
        assert float(jnp.abs(got - hc.sinkhorn(m, 20, 1e-6)).max()) > 1e-6


@pytest.mark.parametrize("seed", [0, 1])
def test_read_and_write_equal_the_reference_mix(seed):
    x, p = _streams(seed, b=1), _params(seed)
    y = jax.random.normal(jax.random.PRNGKey(seed + 9), (1, 5, D))
    pre, post, res = hc.maps(x, p, dims=DIMS, norm_eps=1e-5)
    with jax.default_matmul_precision("highest"):
        want, u = ref.hc_sublayer(x[0], p, CFG, lambda u: (y[0], u))
    np.testing.assert_allclose(hc.read(x, pre)[0], u, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hc.write(x, y, post, res)[0], want, rtol=1e-5,
                               atol=1e-5)


def test_entry_copies_and_exit_sums():
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 3, D))
    x = hc.expand(h, N)
    assert x.shape == (2, 3, N, D)
    np.testing.assert_array_equal(x[:, :, 2], h)
    np.testing.assert_allclose(hc.collapse(x), N * h, rtol=1e-6)


def _lm(hc_dims=None, **kw):
    return TransformerLM(vocab_size=64, d_model=D, num_heads=4, num_layers=2,
                         d_ff=48, max_len=32, pos_encoding="rope", seed=5,
                         hc=hc_dims, **kw).init()


@pytest.mark.parametrize("kw", [{}, {"norm": "rmsnorm", "num_experts": 4,
                                     "experts_per_token": 2}],
                         ids=["mlp", "moe"])
def test_hc_none_is_the_old_block_bit_for_bit(kw):
    """``hc=None``: the same parameters, the same lowered program as a model
    built without the argument, the same logits."""
    toks = jnp.arange(12, dtype=jnp.int32)[None] % 64
    old = TransformerLM(vocab_size=64, d_model=D, num_heads=4, num_layers=2,
                        d_ff=48, max_len=32, pos_encoding="rope", seed=5,
                        **kw).init()
    new = _lm(None, **kw)
    assert "hc1" not in new.params["blocks"][0]
    text = [jax.jit(m.forward).lower(m.params, toks).as_text()
            for m in (old, new)]
    assert text[0] == text[1] and "hc." not in text[0]
    np.testing.assert_array_equal(old.forward(old.params, toks),
                                  new.forward(new.params, toks))


def test_plain_maps_are_the_plain_residual():
    """H_res = I, H_pre = 1/n, H_post = 1 keeps every stream a copy of the
    plain residual: with those maps the model is the model without hc."""
    lm, plain = _lm(DIMS), _lm(None)
    big = 50.0      # exp(50) on the diagonal: Sinkhorn's fixed point is I
    for blk in lm.params["blocks"]:
        for name in ("hc1", "hc2"):
            blk[name]["phi"] = jnp.zeros_like(blk[name]["phi"])
            blk[name]["b"] = jnp.concatenate([
                jnp.full((N,), float(np.log(1 / (N - 1)))),     # sigmoid: 1/n
                jnp.zeros((N,)), big * jnp.eye(N).reshape(-1)])
    params = {**lm.params, "blocks": [
        {**b, **{k: v for k, v in pb.items() if not k.startswith("hc")}}
        for b, pb in zip(lm.params["blocks"], plain.params["blocks"])]}
    toks = jnp.arange(10, dtype=jnp.int32)[None]
    got = lm.forward(params, toks)
    # the exit sums n copies: the final norm takes the factor out
    want = plain.forward(plain.params, toks)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_hc_with_a_module_is_refused():
    with pytest.raises(ValueError, match="hyper-connections"):
        TransformerLM(vocab_size=8, d_model=D, num_heads=4, hc=DIMS,
                      pos_encoding="rope", mixers=("mla",) * 4,
                      mla={"kv_lora_rank": 8, "qk_nope_head_dim": 8,
                           "qk_rope_head_dim": 4, "v_head_dim": 8},
                      mtp={"loss_weight": 0.1})


def test_generate_with_hc_matches_forward():
    lm = _lm(DIMS)
    for blk in lm.params["blocks"]:
        for j, name in enumerate(("hc1", "hc2")):
            blk[name].update(_params(11 + j))
    prompt = jnp.arange(6, dtype=jnp.int32)[None]
    out = np.asarray(lm.generate(prompt, 5))[0]
    seq = list(np.asarray(prompt[0]))
    for _ in range(5):
        logits = lm.forward(lm.params, jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert list(out) == seq
