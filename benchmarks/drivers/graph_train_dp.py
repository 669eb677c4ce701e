"""Driver ``graph_train_dp``: a ``ComputationGraph`` trained data-parallel
through ``ParallelWrapper.fit_epochs`` over the cell's chips.

The whole seeded synthetic training set goes through the program's normal
path into the sharded HBM epoch cache; each ``fit_epochs(cache, E)`` call is
one donated SPMD program of E epochs x N steps, timed to the arrival of its
loss history on the host. ``train_mfu`` comes from the median call.

Images are ``(1 - signal) * noise + signal * template[class]`` so that the
loss has something to learn.

``correct`` holds both ends of training to the plain float32 reference
(``lib/reference_resnet.py``): the mean loss of the first epoch against the
reference's own first optimizer steps from the same initial weights on the
same batches (forward with batch statistics, backward, Adam and, through the
global batch, the gradient all-reduce), and the trained network's outputs on a
probe batch against the reference forward on the same weights. The epoch
program visits the batches in an order of its own; the reference takes them as
they come, which moves the first epoch's mean loss by far less than the
tolerance (PERF.md section 4).

Workload file keys: ``train.global_batch``, ``train.epochs_per_call``,
``train.signal``, ``train.mesh``, ``check.{output_rel_tol,probe_samples,
first_epoch_rel_tol,reference_micro_batch}``. From the program: ``resnet18``,
``ParallelWrapper`` (``build_epoch_cache``, ``fit_epochs``), the network's
``params`` / ``net_state`` / ``output`` and the metrics registry's
``train_chunk_dispatches_total``.
"""

from __future__ import annotations

import statistics
import time

import jax
import numpy as np

from benchmarks.lib import flops, peaks, reference_resnet
from benchmarks.lib.outcome import Outcome


def make_data(cfg, signal, seed):
    rng = np.random.default_rng([seed, 0xC1FA])
    n, size, ch = cfg["train_samples"], cfg["image_size"], cfg["image_channels"]
    classes = cfg["num_classes"]
    y = rng.integers(0, classes, n)
    templates = rng.random((classes, size, size, ch), np.float32)
    x = rng.random((n, size, size, ch), np.float32)
    x *= np.float32(1.0 - signal)
    x += np.float32(signal) * templates[y]
    return x, np.eye(classes, dtype=np.float32)[y]


def run(ctx) -> Outcome:
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import resnet18
    from deeplearning4j_tpu.monitor import metrics
    from deeplearning4j_tpu.parallel import ParallelWrapper, build_mesh
    from deeplearning4j_tpu.parallel.mesh import MeshSpec

    cfg, tr = ctx.config, ctx.cell["train"]
    batch, epochs = int(tr["global_batch"]), int(tr["epochs_per_call"])
    n_batches = cfg["train_samples"] // batch
    with ctx.spans.span("make_data"):
        x, y = make_data(cfg, float(tr["signal"]), ctx.seed)
    net = resnet18(num_classes=cfg["num_classes"], seed=ctx.seed,
                   lr=cfg["learning_rate"],
                   dtype_policy=cfg["dtype_policy"],
                   image_channels=cfg["image_channels"]).init()
    params0 = jax.device_get(net.params)      # for the reference's steps
    mesh = build_mesh(MeshSpec(**tr["mesh"]), devices=ctx.devices)
    wrapper = ParallelWrapper(net, mesh=mesh)
    with ctx.spans.span("build_cache"):
        cache = wrapper.build_epoch_cache(
            ListDataSetIterator(DataSet(x, y), batch))
    if cache is None:
        raise RuntimeError("the training set did not fit the HBM epoch cache")
    on = {s.device for stack in cache.features
          for s in stack.addressable_shards}
    if on != set(ctx.devices):
        raise RuntimeError(f"epoch cache on {len(on)} of {len(ctx.devices)} "
                           "devices")

    launches = metrics().counter("train_chunk_dispatches_total")

    def call():
        d0 = sum(launches.series().values())
        hist = wrapper.fit_epochs(cache, epochs)
        if hist is None:
            raise RuntimeError("fit_epochs fell back off the fused path")
        return (np.asarray(hist, np.float32),
                int(sum(launches.series().values()) - d0))

    with ctx.spans.span("warmup"):
        first_hist, _ = call()

    hists, dispatches = [], []
    t0 = ctx.begin_window()
    while time.monotonic() - t0 < ctx.seconds:
        with ctx.spans.span("dsl_chunk"):
            h, d = call()
        hists.append(h)
        dispatches.append(d)
        ctx.tick()
    ctx.end_window()

    call_s = ctx.before_trace("dsl_chunk")
    median_s = statistics.median(call_s)
    per_call = epochs * n_batches * batch
    chips = len(ctx.devices)
    peak = (1.0 if ctx.rehearse else
            peaks.peaks_for(ctx.devices[0].device_kind)["bf16_flops"])
    flops_sample = flops.resnet_train_flops_per_sample(cfg)
    mfu = 100.0 * per_call / median_s * flops_sample / (chips * peak)

    # ---- correct
    all_hist = np.concatenate([first_hist] + hists)
    finite = bool(np.all(np.isfinite(all_hist)))
    fell = float(all_hist[-1].mean()) < float(first_hist[0].mean())
    one_dispatch = all(d == 1 for d in dispatches)
    # replicas of one weight must be the same bits on every chip
    same = all(
        all(np.array_equal(np.asarray(leaf.addressable_shards[0].data),
                           np.asarray(s.data))
            for s in leaf.addressable_shards[1:])
        for leaf in jax.tree_util.tree_leaves(net.params))
    # the first epoch against the reference's own first optimizer steps
    check = ctx.cell["check"]
    ref_first = reference_resnet.first_losses(
        params0, [(x[i * batch:(i + 1) * batch], y[i * batch:(i + 1) * batch])
                  for i in range(n_batches)], cfg,
        micro=int(check["reference_micro_batch"]), lr=cfg["learning_rate"])
    first_rel = abs(float(first_hist[0].mean()) / np.mean(ref_first) - 1.0)
    # the trained network against the reference forward
    k = int(check["probe_samples"])
    got = np.asarray(net.output(x[:k])[0], np.float32)
    host = jax.device_get((net.params, net.net_state))
    want = np.asarray(reference_resnet.probabilities(
        host[0], host[1], x[:k], cfg))
    # in log space: a trained network's probabilities saturate, their logs
    # still carry the logits' differences
    lg, lw = (np.log(np.maximum(p, 1e-30)) for p in (got, want))
    rel = float(np.max(np.abs(lg - lw)) / np.max(np.abs(lw)))
    ok = (finite and fell and one_dispatch and same
          and first_rel <= float(check["first_epoch_rel_tol"])
          and rel <= float(check["output_rel_tol"]))
    return Outcome(
        correct=ok, attempted=len(hists), failed=0,
        end_to_end={"train_mfu": mfu},
        counters={"chunks": len(hists), "median_chunk_ms": 1e3 * median_s,
                  "steps_per_chunk": epochs * n_batches,
                  "items_per_step": batch, "flops_per_item": flops_sample,
                  "dispatches_per_chunk": max(dispatches),
                  "cache_bytes": cache.nbytes},
        notes=[f"train: chips={chips} calls={len(hists)} "
               f"samples_per_s_per_chip_median_call="
               f"{per_call / median_s / chips:.1f} "
               f"call_ms_median={1e3 * median_s:.2f} "
               f"call_ms_min={1e3 * min(call_s):.2f} "
               f"call_ms_max={1e3 * max(call_s):.2f} "
               f"steps_per_call={epochs * n_batches} global_batch={batch}",
               f"check: first_epoch_losses="
               f"{[round(float(v), 5) for v in first_hist[0]]} "
               f"reference_first_steps={[round(v, 5) for v in ref_first]} "
               f"first_epoch_mean_rel={first_rel:.2e} "
               f"tol={check['first_epoch_rel_tol']} "
               f"loss_last_epoch={float(all_hist[-1].mean()):.5f} "
               f"finite={finite} dispatches_per_chunk={max(dispatches)} "
               f"replicas_equal={same} output_vs_reference_rel={rel:.2e} "
               f"tol={check['output_rel_tol']}"])
