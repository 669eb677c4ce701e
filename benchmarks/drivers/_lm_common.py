"""Shared by the LM drivers: build the program's ``TransformerLM`` from a
Hugging Face style configuration and make its weights on the device.

The weights are data, so the benchmark makes them: one jitted call from the
seed, in the policy's parameter type, with the distributions of
``TransformerLM.init`` (normal * 0.02 embedding, Glorot-normal matrices, unit
norms, zero biases) and, for training, zero Adam moments beside them. Both
trees must have the structure, shapes and types the program's own ``init``
gives; ``_check_tree`` holds them to ``jax.eval_shape`` of it and fails loudly
if the program's trees have changed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def build_lm(config: dict, *, policy: str, seed: int, max_len: int,
             lr: float = 3e-4, remat: bool = False, attn_impl: str = "auto"):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], max_len=max_len, lr=lr, seed=seed,
        dtype_policy=policy, attn_impl=attn_impl, remat=remat,
        pos_encoding="rope", num_kv_heads=config["num_key_value_heads"],
        attn_window=config.get("sliding_window"))


def _init_fn(lm):
    d, f, v = lm.d_model, lm.d_ff, lm.vocab_size
    kv = lm.num_kv_heads * (d // lm.num_heads)
    dt = lm.policy.param_dtype

    def dense(key, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dt)
        return jax.random.normal(key, (fan_in, fan_out), dt) * scale

    def norm():
        return {"g": jnp.ones((d,), dt), "b": jnp.zeros((d,), dt)}

    def init(key):
        keys = jax.random.split(key, 1 + 6 * lm.num_layers)
        blocks = []
        for i in range(lm.num_layers):
            k = keys[1 + 6 * i:7 + 6 * i]
            blocks.append({
                "ln1": norm(),
                "attn": {"wq": dense(k[0], d, d), "wk": dense(k[1], d, kv),
                         "wv": dense(k[2], d, kv), "wo": dense(k[3], d, d)},
                "ln2": norm(),
                "mlp": {"w1": dense(k[4], d, f), "b1": jnp.zeros((f,), dt),
                        "w2": dense(k[5], f, d), "b2": jnp.zeros((d,), dt)},
            })
        return {"embed": jax.random.normal(keys[0], (v, d), dt) * 0.02,
                "ln_f": norm(), "blocks": blocks}

    return jax.jit(init)


def _check_tree(lm, attr: str, got) -> None:
    """``got`` (abstract or real) against ``TransformerLM.init().<attr>``."""
    from deeplearning4j_tpu.models.transformer import TransformerLM

    def spec(tree):
        return jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), tree)

    want = jax.eval_shape(
        lambda: getattr(TransformerLM(**lm.get_config()).init(), attr))
    if spec(want) != spec(got):
        raise RuntimeError(
            f"the program's {attr} tree no longer matches the benchmark's:\n"
            f" program: {spec(want)}\n benchmark: {spec(got)}")


def make_params(lm, seed: int):
    """Weights on the device from ``seed``, one jitted call. Returns
    ``(params, init)`` so a caller can make the same weights again."""
    init = _init_fn(lm)
    key = jax.random.PRNGKey(seed)
    _check_tree(lm, "params", jax.eval_shape(init, key))
    return init(key), init


def make_adam_state(lm):
    """Zero Adam moments beside every weight of ``lm.params``, one jitted
    call."""
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: {"m": jnp.zeros_like(x), "v": jnp.zeros_like(x)}, p))
    _check_tree(lm, "opt_state", jax.eval_shape(zeros, lm.params))
    return zeros(lm.params)
