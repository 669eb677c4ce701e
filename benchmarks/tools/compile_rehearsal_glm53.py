"""``compile_rehearsal.py`` for the hyper-connection cell
(``glm53-serve-agent``): its decode program and its prefill rungs at the timed
sizes, compiled for a described ``v5e:2x2``, with ``memory_analysis()``.
Nothing runs and nothing here is a chip number.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_rehearsal_glm53.py [decode] [prefill] [--rungs 57344,4096]

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


def _model(one_chip):
    from benchmarks.drivers import lm_serve_hc as drv
    from deeplearning4j_tpu.serving import kv_cache

    cell = _load("benchmarks/workloads/glm53-serve-agent.json")
    cfg = _load("benchmarks/configs/glm-5.3-flash-l5.json")
    sv = cell["server"]
    lm = drv.build_lm(cfg, policy=sv["policy"], seed=0,
                      max_len=int(sv["max_len"]))
    shapes = jax.eval_shape(
        lambda: type(lm)(**lm.get_config()).init().params)
    layout = kv_cache.pool_layout(lm, int(sv["slots"]), int(sv["max_len"]),
                                  "bfloat16")
    full = lm.indexers.count("full")
    # the program's names for the layout's kinds (``SlotKVCache.state``)
    kinds = {"latent": layout["latent"], "index": layout["index"][:full],
             "index_open": layout["index"][full:],
             "kda": layout["recurrent"], "conv": layout["conv"]}
    kv = {name: [jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                      sharding=one_chip)
                 for shape, dt in arrays]
          for name, arrays in kinds.items()}
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(shapes))
    print(f"  weights {total / 2 ** 30:.2f} GiB; state by kind (GiB): "
          + ", ".join(f"{k} {kv_cache._layout_nbytes(v) / 2 ** 30:.2f}"
                      for k, v in layout.items()), flush=True)
    return lm, sv, _abstract(shapes, one_chip), kv


def decode(one_chip):
    import deeplearning4j_tpu.serving.engine as eng

    lm, sv, params, kv = _model(one_chip)
    slots = int(sv["slots"])
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    loop = {"cursors": vec, "tok": vec, "remaining": vec,
            "keys": jax.ShapeDtypeStruct((slots, 2), jnp.uint32,
                                         sharding=one_chip)}
    fn = jax.jit(functools.partial(
        eng._serve_decode_loop_impl, lm, eng._row_sampler(0.0, None)),
        donate_argnums=(1,))
    _report(f"glm53 decode {slots} slots x {sv['max_len']}",
            lambda: fn.lower(params, kv, loop).compile())


def prefill(one_chip, rungs):
    import deeplearning4j_tpu.serving.engine as eng

    lm, sv, params, kv = _model(one_chip)
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
        _report(f"glm53 prefill rung {p} into {sv['slots']} slots, one block "
                f"of {p // eng.prefill_block_count(p, p)}",
                lambda: fn.lower(params, kv, carry, prompt, scalar, scalar,
                                 key, scalar).compile())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("programs", nargs="*", default=["decode", "prefill"])
    ap.add_argument("--rungs", default="57344,4096")
    args = ap.parse_args()

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    _force_mosaic()
    # the routed experts' reached form asks the same question of its own
    import deeplearning4j_tpu.models.routed_experts as routed

    routed.flash_default_interpret = lambda: False
    print("compile rehearsal (glm53) for a described v5e:2x2 -- nothing runs, "
          "none of this is a chip number", flush=True)
    if "decode" in args.programs:
        decode(one_chip)
    if "prefill" in args.programs:
        prefill(one_chip, [int(s) for s in args.rungs.split(",")])


if __name__ == "__main__":
    main()
