"""``compile_rehearsal.py`` for the cell whose server drafts from the model's
own module (``gigachat-serve-assist``): its round program, its prefill rungs
and the reference check's program at the timed sizes, compiled for a described
``v5e:2x2``, with ``memory_analysis()``. Nothing runs and nothing here is a
chip number.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_rehearsal_mtp.py [round] [prefill] [check] [--rungs 4096,2048]

The report, the abstract arguments and the switch that puts the kernels on
their Mosaic path are ``compile_rehearsal``'s, by import.
"""

from __future__ import annotations

import argparse
import functools

from compile_rehearsal import (  # noqa: F401  (sets the environment first)
    _abstract, _force_mosaic, _load, _report)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

CELL = "benchmarks/workloads/gigachat-serve-assist.json"
CONFIG = "benchmarks/configs/gigachat3.1-702b-a36b-l5.json"


def _model(one_chip):
    from benchmarks.drivers import lm_serve_mtp as drv
    from deeplearning4j_tpu.serving import kv_cache

    cell, cfg = _load(CELL), _load(CONFIG)
    sv = cell["server"]
    lm = drv.build_lm(cfg, policy=sv["policy"], seed=0,
                      max_len=int(sv["max_len"]))
    shapes = jax.eval_shape(
        lambda: type(lm)(**lm.get_config()).init().params)
    layout = kv_cache.pool_layout(lm, int(sv["slots"]), int(sv["max_len"]),
                                  "bfloat16")
    kv = {"latent": [jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                          sharding=one_chip)
                     for shape, dt in layout["latent"]]}
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(shapes))
    print(f"  weights {total / 2 ** 30:.2f} GiB ({total / 4e6:.0f} M "
          "parameters); state by kind (GiB): "
          + ", ".join(f"{k} {kv_cache._layout_nbytes(v) / 2 ** 30:.2f}"
                      for k, v in layout.items()), flush=True)
    return lm, cell, cfg, _abstract(shapes, one_chip), kv


def rounds(one_chip):
    import deeplearning4j_tpu.serving.engine as eng

    lm, cell, _, params, kv = _model(one_chip)
    slots = int(cell["server"]["slots"])
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    loop = {"cursors": vec, "tok": vec, "remaining": vec, "draft": vec,
            "keys": jax.ShapeDtypeStruct((slots, 2), jnp.uint32,
                                         sharding=one_chip)}
    fn = jax.jit(functools.partial(eng._serve_mtp_impl, lm, None, True, 1),
                 donate_argnums=(1,))
    _report(f"gigachat round {slots} slots x {cell['server']['max_len']}",
            lambda: fn.lower(params, kv, loop).compile())


def prefill(one_chip, rungs):
    import deeplearning4j_tpu.serving.engine as eng

    lm, cell, _, params, kv = _model(one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    for p in rungs:
        prompt = jax.ShapeDtypeStruct((1, p), jnp.int32, sharding=one_chip)
        carry = {name: jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                            sharding=one_chip)
                 for name, (shape, dt, _) in eng.prefill_carry_layout(
                     lm, p).items()}
        fn = jax.jit(functools.partial(
            eng._serve_prefill_block_impl, lm, eng._row_sampler(0.0, None)),
            donate_argnums=(1, 2))
        _report(f"gigachat prefill rung {p} into {cell['server']['slots']} "
                f"slots, one block of {p // eng.prefill_block_count(p, p)}",
                lambda: fn.lower(params, kv, carry, prompt, scalar, scalar,
                                 key, scalar).compile())


def check(one_chip):
    """The reference's teacher-forced forward at the check's padded length:
    it runs beside the weights once the pool is gone."""
    from benchmarks.drivers import lm_serve_mtp as drv
    from benchmarks.lib import reference_gigachat_mtp as ref

    lm, cell, cfg, params, _ = _model(one_chip)
    t = int(cell["traffic"]["max_total_tokens"])
    n_tail = int(cell["traffic"]["output_tokens"]["max"])
    k, layers = lm.experts_per_token, lm.n_layers("moe")
    tokens = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one_chip)
    chosen = jax.ShapeDtypeStruct((layers, t, k), jnp.int32,
                                  sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    fn = ref._jit_tail(ref._key(drv.reference_config(cfg)), n_tail, False)
    with jax.default_matmul_precision("highest"):
        _report(f"gigachat reference check, {t} positions",
                lambda: fn.lower(params, tokens, scalar, chosen,
                                 tokens).compile())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("programs", nargs="*",
                    default=["round", "prefill", "check"])
    ap.add_argument("--rungs", default="4096,2048")
    args = ap.parse_args()

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    _force_mosaic()
    # the routed experts' reached form asks the same question of its own
    import deeplearning4j_tpu.models.routed_experts as routed

    routed.flash_default_interpret = lambda: False
    print("compile rehearsal (gigachat mtp) for a described v5e:2x2 -- "
          "nothing runs, none of this is a chip number", flush=True)
    if "round" in args.programs:
        rounds(one_chip)
    if "prefill" in args.programs:
        prefill(one_chip, [int(s) for s in args.rungs.split(",")])
    if "check" in args.programs:
        check(one_chip)


if __name__ == "__main__":
    main()
