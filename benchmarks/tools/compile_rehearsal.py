"""Compile the cells' real-size programs for a described ``v5e:2x2`` and print
``memory_analysis()`` -- before any chip time is spent.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_rehearsal.py [lm_train] [decode] [prefill] [resnet] [--slots 64,128] [--batch 1024,4096]

Nothing runs and nothing here is a chip number (on-chip-measurement guide,
section 2.3): the TPU compiler is installed, the chip is described, and a
program that does not fit the device's memory or a kernel Mosaic refuses
fails here at no cost. Code that asks ``jax.devices()`` sees the CPU, so this
script forces flash attention onto its Mosaic path itself, and hands the
four-device ResNet chunk program the described devices in place of the mesh
``ParallelWrapper`` built.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
for _k in [k for k in os.environ if k.startswith("DL4J_")]:
    del os.environ[_k]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402

GIB = 1024.0 ** 3


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _report(name, compile_fn):
    t0 = time.monotonic()
    try:
        compiled = compile_fn()
    except Exception as e:  # what the chip's compiler would raise
        first = str(e).strip().splitlines()[0][:300]
        print(f"{name}: REFUSED after {time.monotonic() - t0:.0f}s: {first}",
              flush=True)
        return None
    m = compiled.memory_analysis()
    args, out, temp = (m.argument_size_in_bytes, m.output_size_in_bytes,
                       m.temp_size_in_bytes)
    alias = m.alias_size_in_bytes
    print(f"{name}: arguments {args / GIB:.2f} + temp {temp / GIB:.2f} "
          f"= {(args + temp) / GIB:.2f} GiB (outputs {out / GIB:.2f}, "
          f"aliased {alias / GIB:.2f}); compile "
          f"{time.monotonic() - t0:.0f}s", flush=True)
    return compiled


def _force_mosaic():
    import importlib

    # import_module, not ``import ... as``: the package re-exports a function
    # called flash_attention, which shadows the submodule as an attribute
    for name in ("deeplearning4j_tpu.models.transformer",
                 "deeplearning4j_tpu.pallas.flash_attention"):
        importlib.import_module(name).flash_default_interpret = lambda: False


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _lm(policy, max_len, attn_impl="auto"):
    from benchmarks.drivers import _lm_common as common

    cfg = _load("benchmarks/configs/starcoder2-3b-l4.json")
    lm = common.build_lm(cfg, policy=policy, seed=0, max_len=max_len,
                         attn_impl=attn_impl)
    shapes = jax.eval_shape(common._init_fn(lm), jax.random.PRNGKey(0))
    return cfg, lm, shapes


def lm_train(one_chip, batch=None):
    cell = _load("benchmarks/workloads/sc2-train-8k.json")["train"]
    cfg, lm, shapes = _lm(cell["policy"], cell["seq_len"], cell["attn_impl"])
    params = _abstract(shapes, one_chip)
    opt = jax.tree_util.tree_map(lambda p: {"m": p, "v": p}, params)
    b = batch or cell["batch"]
    tokens = jax.ShapeDtypeStruct((b, cell["seq_len"]), jnp.int32,
                                  sharding=one_chip)
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    lowered = lm.make_train_step().lower(params, opt, tokens, count)
    print(f"  Mosaic custom calls in the lowered step: "
          f"{lowered.as_text().count('tpu_custom_call')}")
    _report(f"lm_train {b} x {cell['seq_len']} L{lm.num_layers}",
            lowered.compile)


def _serve_args(lm, shapes, one_chip, slots, max_len):
    dh = lm.d_model // lm.num_heads
    pool = jax.ShapeDtypeStruct(
        (lm.num_layers, slots, max_len, lm.num_kv_heads, dh), jnp.bfloat16,
        sharding=one_chip)
    return _abstract(shapes, one_chip), {"k": pool, "v": pool}


def decode(one_chip, slots_list):
    import deeplearning4j_tpu.serving.engine as eng

    sv = _load("benchmarks/workloads/sc2-serve-steady.json")["server"]
    cfg, lm, shapes = _lm(sv["policy"], sv["max_len"])
    for slots in slots_list:
        params, kv = _serve_args(lm, shapes, one_chip, slots, sv["max_len"])
        vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
        keys = jax.ShapeDtypeStruct((slots, 2), jnp.uint32, sharding=one_chip)
        fn = jax.jit(functools.partial(
            eng._serve_decode_impl, lm, eng._row_sampler(0.0, None)),
            donate_argnums=(1,))
        _report(f"decode {slots} slots x {sv['max_len']}",
                lambda: fn.lower(params, kv, vec, vec, keys).compile())


def prefill(one_chip, rungs):
    import deeplearning4j_tpu.serving.engine as eng

    sv = _load("benchmarks/workloads/sc2-serve-steady.json")["server"]
    cfg, lm, shapes = _lm(sv["policy"], sv["max_len"])
    params, kv = _serve_args(lm, shapes, one_chip, sv["slots"], sv["max_len"])
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    for p in rungs:
        prompt = jax.ShapeDtypeStruct((1, p), jnp.int32, sharding=one_chip)
        fn = jax.jit(functools.partial(
            eng._serve_prefill_impl, lm, eng._row_sampler(0.0, None), False),
            donate_argnums=(1,))
        _report(f"prefill rung {p} into {sv['slots']} slots",
                lambda: fn.lower(params, kv, prompt, scalar, scalar,
                                 key).compile())


def resnet(topo, batches):
    """The four-device chunk program of ``ParallelWrapper.fit_epochs``: the
    wrapper is built on four virtual CPU devices, then the same pure chunk
    function is jitted with every sharding moved onto the described mesh."""
    from deeplearning4j_tpu.models import resnet18
    from deeplearning4j_tpu.parallel import ParallelWrapper, build_mesh
    from deeplearning4j_tpu.parallel.mesh import MeshSpec
    from deeplearning4j_tpu.resilience.guard import nan_guard_policy

    cell = _load("benchmarks/workloads/resnet18-dp4.json")["train"]
    cfg = _load("benchmarks/configs/resnet18-cifar10.json")
    cpu_mesh = build_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
    tpu_mesh = Mesh(np.array(topo.devices[:4]), cpu_mesh.axis_names)

    def move(x):
        spec = getattr(x.sharding, "spec", None)
        sh = NamedSharding(tpu_mesh, spec if spec is not None
                           else jax.sharding.PartitionSpec())
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    for batch in batches:
        n = cfg["train_samples"] // batch
        net = resnet18(num_classes=cfg["num_classes"],
                       dtype_policy=cfg["dtype_policy"]).init()
        wrapper = ParallelWrapper(net, mesh=cpu_mesh)
        repl = NamedSharding(cpu_mesh, jax.sharding.PartitionSpec())
        batch_sh = NamedSharding(
            cpu_mesh, jax.sharding.PartitionSpec(None, cpu_mesh.axis_names[0]))

        def stack(shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=batch_sh)

        size = cfg["image_size"]
        feats = stack((n, batch, size, size, cfg["image_channels"]))
        labels = stack((n, batch, cfg["num_classes"]))
        lmask = stack((n, batch))
        keys = jax.ShapeDtypeStruct((cell["epochs_per_call"], 2), jnp.uint32,
                                    sharding=repl)
        state = jax.tree_util.tree_map(
            move, (net.params, net.updater_state, net.net_state))
        scal = [jax.ShapeDtypeStruct((), d, sharding=NamedSharding(
            tpu_mesh, jax.sharding.PartitionSpec()))
            for d in (jnp.int32, jnp.float32)]
        data = [(move(feats),), (move(labels),), None, (move(lmask),),
                move(keys)]
        guarded = nan_guard_policy() != "off"
        r = NamedSharding(tpu_mesh, jax.sharding.PartitionSpec())
        out = (jax.tree_util.tree_map(lambda _: r, net.params),
               jax.tree_util.tree_map(lambda _: r, net.updater_state),
               r, r) + ((r,) if guarded else ())
        fn = jax.jit(net._epoch_run_fn(True, 1, guarded, 0),
                     donate_argnums=(0, 1, 2), out_shardings=out)

        def compile_it():
            with tpu_mesh:
                return fn.lower(*state, *scal, *data).compile()

        compiled = _report(
            f"resnet18 data=4 global batch {batch} ({batch // 4} a chip), "
            f"{cell['epochs_per_call']} x {n} steps (per device)", compile_it)
        if compiled is not None:
            text = compiled.as_text()
            print(f"  all-reduce ops in the module: "
                  f"{text.count(' all-reduce(') + text.count(' all-reduce-start(')}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("programs", nargs="*",
                    default=["lm_train", "decode", "prefill", "resnet"])
    ap.add_argument("--slots", default="64,128")
    ap.add_argument("--rungs", default="16384")
    ap.add_argument("--batch", default=None,
                    help="ResNet global batches (default: the cell's)")
    ap.add_argument("--lm-batch", type=int, default=None)
    args = ap.parse_args()

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    _force_mosaic()
    print("compile rehearsal for a described v5e:2x2 -- nothing runs, "
          "none of this is a chip number", flush=True)
    if "lm_train" in args.programs:
        lm_train(one_chip, batch=args.lm_batch)
    if "decode" in args.programs:
        decode(one_chip, [int(s) for s in args.slots.split(",")])
    if "prefill" in args.programs:
        prefill(one_chip, [int(s) for s in args.rungs.split(",")])
    if "resnet" in args.programs:
        cell = _load("benchmarks/workloads/resnet18-dp4.json")["train"]
        batches = ([int(b) for b in args.batch.split(",")] if args.batch
                   else [cell["global_batch"]])
        resnet(topo, batches)


if __name__ == "__main__":
    main()
