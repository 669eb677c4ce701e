"""``compile_rehearsal.py`` for the window-and-full-attention cell
(``mellum2-serve-mixed``): its decode program and its prefill rungs at the
timed sizes, compiled for a described ``v5e:2x2``, with ``memory_analysis()``.
Nothing runs and nothing here is a chip number.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_rehearsal_swa.py [decode] [prefill] [--rungs 28672,16384,256] [--slots 64]

(run from ``benchmarks/tools/``.) The report, the abstract arguments and the
switch that puts flash attention and the experts' kernel on their Mosaic path
are ``compile_rehearsal``'s, by import. ``decode`` also says whether the
compiled decode program copies a pool: any instruction whose result has the
shape of the ``T_max`` pool or of the ring and is no parameter, no scatter
into it and no tuple of the outputs.
"""

from __future__ import annotations

import argparse
import functools
import re

from compile_rehearsal import (  # noqa: F401  (sets the environment first)
    _abstract, _force_mosaic, _load, _report)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def _model(one_chip, slots):
    from benchmarks.drivers import lm_serve_swa as drv
    from deeplearning4j_tpu.serving import kv_cache

    cell = _load("benchmarks/workloads/mellum2-serve-mixed.json")
    cfg = _load("benchmarks/configs/mellum2-12b-a2.5b-l4.json")
    sv = dict(cell["server"], slots=slots or cell["server"]["slots"])
    lm = drv.build_lm(cfg, policy=sv["policy"], seed=0,
                      max_len=int(sv["max_len"]))
    shapes = jax.eval_shape(
        lambda: type(lm)(**lm.get_config()).init().params)
    layout = kv_cache.pool_layout(lm, int(sv["slots"]), int(sv["max_len"]),
                                  "bfloat16")

    def arrays(kind):
        return [jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
                for shape, dt in layout[kind]]

    kv = dict(zip(("k", "v", "kw", "vw"), arrays("kv") + arrays("ring")))
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(shapes))
    print(f"  weights {total / 2 ** 30:.2f} GiB; state by kind (GiB): "
          + ", ".join(f"{k} {kv_cache._layout_nbytes(v) / 2 ** 30:.3f}"
                      for k, v in layout.items() if v), flush=True)
    return lm, sv, _abstract(shapes, one_chip), kv


def pool_copies(text: str, kv) -> list:
    """Lines of the compiled program's text that make a value of a pool's
    shape other than by a parameter, an in-place scatter / dynamic-update, a
    bitcast or a tuple: a copy or a re-laying of the pool."""
    found = []
    for a in kv.values():
        shape = "bf16[" + ",".join(map(str, a.shape)) + "]"
        for line in text.splitlines():
            if " = " + shape in line.replace("{", " {") and not re.search(
                    r"parameter\(|scatter|dynamic-update-slice|bitcast|"
                    r"get-tuple-element|fusion\(.*kind=kLoop.*"
                    r"dynamic_update|custom-call", line):
                found.append(line.strip()[:200])
    return found


def decode(one_chip, slots):
    import deeplearning4j_tpu.serving.engine as eng

    lm, sv, params, kv = _model(one_chip, slots)
    slots = int(sv["slots"])
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    loop = {"cursors": vec, "tok": vec, "remaining": vec,
            "keys": jax.ShapeDtypeStruct((slots, 2), jnp.uint32,
                                         sharding=one_chip)}
    fn = jax.jit(functools.partial(
        eng._serve_decode_loop_impl, lm, eng._row_sampler(0.0, None)),
        donate_argnums=(1,))
    compiled = []

    def build():
        compiled.append(fn.lower(params, kv, loop).compile())
        return compiled[0]

    _report(f"swa decode {slots} slots x {sv['max_len']}", build)
    if compiled:
        copies = pool_copies(compiled[0].as_text(), kv)
        print(f"  instructions that copy or re-lay a pool: {len(copies)}",
              flush=True)
        for line in copies[:8]:
            print("    " + line, flush=True)


def prefill(one_chip, rungs, slots):
    import deeplearning4j_tpu.serving.engine as eng

    lm, sv, params, kv = _model(one_chip, slots)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    for p in rungs:
        prompt = jax.ShapeDtypeStruct((1, p), jnp.int32, sharding=one_chip)
        fn = jax.jit(functools.partial(
            eng._serve_prefill_impl, lm, eng._row_sampler(0.0, None), False),
            donate_argnums=(1,))
        _report(f"swa prefill rung {p} into {sv['slots']} slots",
                lambda: fn.lower(params, kv, prompt, scalar, scalar,
                                 key).compile())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("programs", nargs="*", default=["decode", "prefill"])
    ap.add_argument("--rungs", default="28672,16384,256")
    ap.add_argument("--slots", type=int, default=0)
    args = ap.parse_args()

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    _force_mosaic()
    # the pool read and the reached experts choose their kernels by names of
    # their own
    import deeplearning4j_tpu.models.routed_experts as experts
    import deeplearning4j_tpu.serving.engine as eng

    eng.flash_default_interpret = experts.flash_default_interpret = (
        lambda: False)
    print("compile rehearsal (swa) for a described v5e:2x2 -- nothing runs, "
          "none of this is a chip number", flush=True)
    if "decode" in args.programs:
        decode(one_chip, args.slots)
    if "prefill" in args.programs:
        prefill(one_chip, [int(s) for s in args.rungs.split(",")],
                args.slots)


if __name__ == "__main__":
    main()
