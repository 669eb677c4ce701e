"""Shared by the drivers of a routed-experts LM (OLMoE): build the program's
``TransformerLM`` from a Hugging Face style configuration and make its weights
on the device.

As ``_lm_common.py`` does for the dense block: the weights are data, so the
benchmark makes them, one jitted call from the seed, float32, with the
distributions of ``TransformerLM.init`` (normal * 0.02 embedding and head,
Glorot-normal matrices, unit norm gains). The tree must have the structure,
shapes and types of the program's own ``init``; it is held to
``jax.eval_shape`` of it, and ``init()`` itself is never called: its Adam
moments would not fit beside 7 GiB of weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def build_lm(config: dict, *, policy: str, seed: int, max_len: int):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], max_len=max_len, seed=seed,
        dtype_policy=policy, pos_encoding="rope",
        num_kv_heads=config["num_key_value_heads"], norm="rmsnorm",
        qk_norm=True, num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        tie_embeddings=config["tie_word_embeddings"])


def _init_fn(lm):
    d, f, v, e = lm.d_model, lm.d_ff, lm.vocab_size, lm.num_experts
    kv = lm.num_kv_heads * (d // lm.num_heads)
    dt = lm.policy.param_dtype

    def glorot(key, shape, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dt)
        return jax.random.normal(key, shape, dt) * scale

    def gain(width=d):
        return {"g": jnp.ones((width,), dt)}

    def init(key):
        keys = jax.random.split(key, 2 + 8 * lm.num_layers)
        blocks = []
        for i in range(lm.num_layers):
            k = keys[2 + 8 * i:10 + 8 * i]
            blocks.append({
                "ln1": gain(),
                "attn": {"wq": glorot(k[0], (d, d), d, d),
                         "wk": glorot(k[1], (d, kv), d, kv),
                         "wv": glorot(k[2], (d, kv), d, kv),
                         "wo": glorot(k[3], (d, d), d, d),
                         "q_norm": gain(), "k_norm": gain(kv)},
                "ln2": gain(),
                "moe": {"router": glorot(k[4], (d, e), d, e),
                        "w_gate": glorot(k[5], (e, d, f), d, f),
                        "w_up": glorot(k[6], (e, d, f), d, f),
                        "w_down": glorot(k[7], (e, f, d), f, d)},
            })
        return {"embed": jax.random.normal(keys[0], (v, d), dt) * 0.02,
                "head": jax.random.normal(keys[1], (v, d), dt) * 0.02,
                "ln_f": gain(), "blocks": blocks}

    return jax.jit(init)


def _check_tree(lm, got) -> None:
    """``got`` (abstract) against the program's ``init().params``."""
    from deeplearning4j_tpu.models.transformer import TransformerLM

    def spec(tree):
        return jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), tree)

    want = jax.eval_shape(
        lambda: TransformerLM(**lm.get_config()).init().params)
    if spec(want) != spec(got):
        raise RuntimeError(
            "the program's params tree no longer matches the benchmark's:\n"
            f" program: {spec(want)}\n benchmark: {spec(got)}")


def make_params(lm, seed: int):
    """Weights on the device from ``seed``, one jitted call."""
    init = _init_fn(lm)
    key = jax.random.PRNGKey(seed)
    _check_tree(lm, jax.eval_shape(init, key))
    return init(key)
