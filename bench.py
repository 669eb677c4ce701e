"""Benchmark: the full BASELINE.md protocol on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "extras": {...}}

Headline = transformer-LM training throughput (tokens/sec/chip) — the
model-FLOP-dominated config. Runs in ONE process on the attached TPU:
``jax.devices()`` is read in-process, a platform other than ``tpu`` exits
non-zero before anything is built, and every artifact is stamped with the
device as JAX reports it. A section that raises is recorded in the
artifact AND makes the exit code non-zero.

Measurement protocol: steady-state per-step timing after a warm-up call,
hard on-device sync before/after the timed window, batches device-resident
(transferred once). Where a fused multi-step program exists, BOTH the
per-dispatch and fused numbers are reported and the fused one is the
headline for that config; the gap is the host-dispatch cost.

``extras`` carries every BASELINE.md config:
  - MNIST MLP, LeNet-5, GravesLSTM char-RNN (fused TBPTT), word2vec
    skip-gram, ResNet-18 CIFAR (bf16) — samples(/words)/sec/chip
  - transformer LM (bf16) — tokens/sec + achieved model TFLOP/s + MFU,
    per-dispatch vs fused, batch sweep, and a t=4096 config where the
    Pallas flash-attention kernel engages
  - GEMM sweep 512–8192 (bf16) — dispatch-chained AND fori-loop-fused
    TFLOP/s per size (fused isolates the chip from the dispatch floor)
  - infeed: async device-prefetch overlap vs synchronous feeding
  - epoch: HBM-cached whole-epoch fusion (fit_epochs) vs streaming
    per-step fit — samples/sec + measured dispatches-per-epoch
  - dp_epoch: the SAME fused pipeline sharded over the data mesh
    (ParallelWrapper.fit_epochs) — weak-scaling samples/sec/chip +
    dispatches-per-epoch (must stay 1 at any device count); skipped
    when only one device is visible
  - mesh_sweep: DP×TP grid under the sharding registry — step time,
    dispatches/chunk (must stay 1 over BOTH axes) and the per-chip
    HBM model per mesh shape; skipped below 4 devices
  - guard: numeric-sentinel overhead (on vs off, <3% target) + async
    checkpoint blocking time
  - telemetry: in-program metrics-pack overhead (on vs off, <3%
    target) + exporter round-trip; every artifact this bench writes —
    including partials and error lines — embeds a metrics+span summary
    block ("telemetry" key) AND the run-ledger goodput/badput report
  - flight: run-ledger + flight-recorder overhead (recorder on vs off,
    <3% target) + the postmortem round trip (completed run's segments
    classify "clean")
  - serve: the continuous-batching decode server under an open-loop
    Poisson stream — p50/p99 latency, TTFT/TPOT, tokens/sec, slot
    occupancy, and compile-count flatness after warmup (plus the
    persisted XLA compilation cache's on-disk stats)
  - serve_fleet: M in-process DecodeServer replicas behind the fleet
    router, the SAME Poisson stream replayed at each fleet size on
    per-replica virtual clocks (real measured dispatch costs booked on
    chip-per-replica timelines) — aggregate tokens/sec scaling 1->2->4,
    p50/p99/TTFT vs the single-replica baseline, routing balance, and
    a failover measurement (one replica killed mid-stream: requeued
    requests must all complete, recovery time reported)

MFU = achieved / peak, peak looked up by ``device_kind`` in ``PEAKS`` (a
device missing from the table is an error, never a default).
Model FLOPs come from the COMPILED program's ``cost_analysis()`` when the
backend provides one (monitor/profile.py), with the analytic formulas
kept as a cross-check: each entry's "flops_source" block carries both
counts and a ``flops_divergence_pct`` field, flagged above 10%. Each
profiled entry also gets a "cost_model" step-time decomposition (optimal
compute vs memory time from the roofline floors vs the measured step —
compute-/memory-bound classification + dispatch wait), and every
artifact — partials and error lines included — embeds the ProgramProfile
blocks collected so far under extras["profile"] plus chunk-boundary HBM
watermarks validating the epoch-cache budget model (the epoch section's
"hbm_budget_check"). Training data is synthetic (zero-egress sandbox;
throughput does not depend on pixel/token values) via the same public
``fit`` APIs a user calls.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from deeplearning4j_tpu.monitor import (
    telemetry_summary as _telemetry_summary,
    tracer as _tracer,
)

# per-chip peaks keyed by ``jax.devices()[0].device_kind``
PEAKS = {
    "TPU v5 lite": {
        "bf16_tflops": 197.0, "hbm_gbps": 819.0,
        "source": "Google Cloud documentation, \"TPU v5e\""},
}


def _peaks_for(device_kind: str) -> dict:
    """``PEAKS[device_kind]``; a device missing from the table is an
    error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s / bandwidth recorded for device_kind="
            f"{device_kind!r}; add it to bench.PEAKS with its source "
            f"(known: {sorted(PEAKS)})") from None


def _peaks() -> dict:
    """The attached device's row of ``PEAKS``."""
    import jax

    return _peaks_for(jax.devices()[0].device_kind)


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


# sections and sub-configs that raised during this run: each is recorded in
# the artifact where it happened AND turns the exit code non-zero
_FAILURES = []


def _record_failure(name, exc) -> str:
    _FAILURES.append(name)
    _log(f"{name} FAILED: {exc!r}")
    return str(exc)[:200]


def _profile_step(fn, args, name):
    """Cost/memory profile of one jitted program (``fn.lower(*args)``
    reads avals only — donated buffers are NOT consumed). The
    cost-analysis FLOPs are the measured-FLOPs source for MFU; the
    analytic formulas stay as the cross-check, with divergence >10%
    flagged in the artifact. Costs one extra XLA compile per profiled
    program; returns None (and logs) when the backend cannot analyze.
    An explicit DL4J_PROFILE=0 opt-out (main() only sets the default)
    skips the capture entirely — no extra compiles, no profile block
    entries."""
    from deeplearning4j_tpu.monitor.profile import (
        capture_program_profile, profile_enabled)

    if not profile_enabled():
        return None
    try:
        prof, _ = capture_program_profile(fn, args, name=name,
                                          key=("bench", name))
    except Exception as e:
        _log(f"profile capture for {name} failed: {e!r}")
        return None
    return prof


def _flops_entry(analytic_flops, analytic_note, prof, per: int):
    """The artifact's dual flops_source block: the analytic formula and
    the compiled cost-analysis count, per sample (or token), plus their
    divergence. ``per`` normalizes the whole-program cost-analysis count
    (one step over ``per`` samples/tokens)."""
    from deeplearning4j_tpu.monitor.profile import flops_divergence_pct

    cost = (None if prof is None or prof.flops is None
            else prof.flops / per)
    div = flops_divergence_pct(analytic_flops, cost)
    return {
        "analytic": analytic_note,
        "analytic_flops": round(float(analytic_flops), 1),
        "cost_analysis_flops": None if cost is None else round(cost, 1),
        "flops_divergence_pct": div,
        "flops_divergence_flag": (div is not None and abs(div) > 10.0),
    }


def _cost_model_entry(prof, measured_s):
    """Step-time decomposition against the compiled cost model: optimal
    device time from the roofline floors vs the measured step —
    classifies the section compute- vs memory-bound and prices the
    dispatch wait."""
    from deeplearning4j_tpu.monitor.profile import classify_boundedness

    if prof is None:
        return None
    entry = classify_boundedness(
        prof.flops, prof.bytes_accessed, measured_s,
        _peaks()["bf16_tflops"] * 1e12, _peaks()["hbm_gbps"] * 1e9)
    entry["peak_hbm_bytes"] = prof.peak_bytes
    entry["compile_s"] = prof.compile_s
    return entry


def _sync(x):
    """Hard sync: reduce one device leaf to a scalar ON DEVICE and read
    that back — a 4-byte readback forces completion of all prior work
    (the chip executes its queue in order) without a full-array transfer
    polluting the measurement."""
    import jax
    import jax.numpy as jnp

    for leaf in jax.tree_util.tree_leaves(x):
        if hasattr(leaf, "addressable_shards") or hasattr(leaf, "devices"):
            float(jnp.sum(jnp.ravel(leaf)[:1]).astype(jnp.float32))
            return
    # no device leaf found (e.g. a network object): sync nothing loudly
    raise TypeError(f"_sync: no device array found in {type(x)}")


def _time_loop(fn, steps, sync=None):
    """Seconds per call. ``sync`` extracts the device data to read back
    (defaults to the call's own return value)."""
    out = fn()  # warm
    _sync(sync() if sync else out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn()
    _sync(sync() if sync else out)
    return (time.perf_counter() - t0) / steps


def _dev(*arrays):
    """Place arrays on device once, synced (steady-state protocol)."""
    import jax

    out = [jax.device_put(a) for a in arrays]
    for o in out:
        _sync(o)
    return out


# ----------------------------------------------------------------------
def bench_gemm():
    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(0)
    sizes = [512, 1024, 2048, 4096, 8192]
    chained, fused = {}, {}
    best = 0.0
    for n in sizes:
        a = jnp.asarray(rng.normal(size=(n, n)), jnp.bfloat16)
        c0 = jnp.asarray(rng.normal(size=(n, n)), jnp.bfloat16)
        f = jax.jit(lambda a, b: a @ b)
        steps = 30 if n <= 2048 else 10
        c = f(a, c0)
        _sync(c)
        t0 = time.perf_counter()
        for _ in range(steps):
            c = f(a, c)  # chained: each call consumes the previous result
        _sync(c)
        sec = (time.perf_counter() - t0) / steps
        tflops_chained = 2 * n ** 3 / sec / 1e12

        # fused: K matmuls inside ONE program — no per-call dispatch.
        # The fori_loop carry keeps each iteration dependent on the last
        # (XLA cannot elide or overlap the chain), exactly like the
        # dispatch-chained loop above minus the host round-trips.
        k = 100 if n <= 2048 else 30

        @jax.jit
        def chain(a, c):
            return lax.fori_loop(0, k, lambda i, cc: a @ cc, c)

        c = chain(a, c0)
        _sync(c)
        t0 = time.perf_counter()
        c = chain(a, c0)
        _sync(c)
        sec = (time.perf_counter() - t0) / k
        tflops_fused = 2 * n ** 3 / sec / 1e12

        for name, val, store in (("chained", tflops_chained, chained),
                                 ("fused", tflops_fused, fused)):
            if val > _peaks()["bf16_tflops"] * 1.05:
                _log(f"gemm {n} {name}: {val:.1f} TFLOP/s exceeds chip "
                     "peak — measurement invalid, discarding")
                store[str(n)] = None
            else:
                store[str(n)] = round(val, 1)
        # headline peak considers BOTH columns: a discarded fused number
        # must not zero the headline while chained data is valid
        for val in (fused[str(n)], chained[str(n)]):
            if val:
                best = max(best, val)
        _log(f"gemm {n}: {tflops_chained:.1f} TFLOP/s chained, "
             f"{tflops_fused:.1f} fused")
    return {
        "per_size_tflops_chained": chained,
        "per_size_tflops_fused": fused,
        "peak_achieved_tflops": round(best, 1),
        "mfu_pct": round(100 * best / _peaks()["bf16_tflops"], 1),
        "note": "fused = lax.fori_loop chain in one program; "
                "chained-vs-fused gap is the per-dispatch floor",
    }


def _fit_throughput(net, ds, batch, steps):
    """Per-step fit AND fused fit_steps samples/sec (both reported).
    Syncs by reading back a parameter leaf (fit returns the network)."""
    sync = lambda: net.params
    stepwise = 1 / _time_loop(lambda: net.fit(ds), steps, sync=sync) * batch
    try:
        fused_fn = lambda: net.fit_steps(ds, 10)
        fused = (1 / (_time_loop(fused_fn, max(2, steps // 10),
                                 sync=sync) / 10) * batch)
    except Exception as e:
        _log(f"fit_steps path FAILED (falling back to fit): {e!r}")
        fused = 0.0
    return stepwise, fused


def bench_mlp():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import mnist_mlp

    rng = np.random.default_rng(0)
    batch = 4096
    x = rng.random((batch, 784), np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    x, y = _dev(x, y)
    net = mnist_mlp(hidden=256, dtype_policy="bf16").init()
    stepwise, fused = _fit_throughput(net, DataSet(x, y), batch, steps=20)
    _log(f"mlp: {fused:,.0f} samples/sec fused ({stepwise:,.0f} per-step)")
    return {"samples_per_sec": round(max(stepwise, fused), 1),
            "per_step": round(stepwise, 1), "fused": round(fused, 1),
            "batch": batch}


def bench_lenet():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import lenet5

    rng = np.random.default_rng(0)
    batch = 1024
    x = rng.random((batch, 28, 28, 1), np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    x, y = _dev(x, y)
    net = lenet5(dtype_policy="bf16").init()
    stepwise, fused = _fit_throughput(net, DataSet(x, y), batch, steps=20)
    _log(f"lenet5: {fused:,.0f} samples/sec fused ({stepwise:,.0f} per-step)")
    return {"samples_per_sec": round(max(stepwise, fused), 1),
            "per_step": round(stepwise, 1), "fused": round(fused, 1),
            "batch": batch}


def bench_char_lstm():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import char_lstm

    rng = np.random.default_rng(0)
    batch, t, vocab = 128, 200, 128
    idx = rng.integers(0, vocab, (batch, t))
    x = np.eye(vocab, dtype=np.float32)[idx]
    y = np.eye(vocab, dtype=np.float32)[np.roll(idx, -1, axis=1)]
    x, y = _dev(x, y)
    net = char_lstm(vocab_size=vocab, hidden=256, layers=2,
                    tbptt_length=50, dtype_policy="bf16").init()
    ds = DataSet(x, y)
    # fit() itself now fuses all TBPTT windows into one scanned program
    sec = _time_loop(lambda: net.fit(ds), steps=5, sync=lambda: net.params)
    sps = batch / sec
    _log(f"char_lstm: {sps:,.0f} samples/sec ({sps * t:,.0f} tokens/sec, "
         "fused TBPTT scan)")
    return {"samples_per_sec": round(sps, 1),
            "tokens_per_sec": round(sps * t, 1),
            "batch": batch, "seq_len": t, "tbptt": 50,
            "path": "fused-tbptt-scan"}


def bench_word2vec():
    """Host pair-loop vs fused whole-epoch skip-gram (ISSUE 18): words/
    sec both ways, the 1-dispatch-per-chunk counter assert, and the
    row-sharded table's per-chip bytes on a 2-device model mesh."""
    import jax

    from deeplearning4j_tpu.nlp.sentence_iterator import (
        CollectionSentenceIterator)
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    rng = np.random.default_rng(0)
    vocab = 5000
    n_sentences, sent_len = 2000, 40
    zipf = rng.zipf(1.3, size=(n_sentences, sent_len)) % vocab
    sentences = [" ".join(f"w{t}" for t in row) for row in zipf]
    words = n_sentences * sent_len

    def make(seed):
        return Word2Vec(CollectionSentenceIterator(sentences),
                        layer_size=128, window_size=5,
                        min_word_frequency=1, negative=5, iterations=1,
                        epochs=1, seed=seed)

    # --- host pair-loop baseline (cold, then warm jit) ---
    w2v = make(42)
    t0 = time.perf_counter()
    w2v.fit()
    host_cold = words / (time.perf_counter() - t0)
    w2v2 = make(43)
    t0 = time.perf_counter()
    w2v2.fit()
    host_wps = words / (time.perf_counter() - t0)

    # --- fused whole-epoch path: E epochs x N batches, ONE dispatch ---
    fused = make(44)
    fused.build_vocab()
    fused.reset_weights()
    cache = fused.build_corpus_cache()
    fused.fit_epochs(1)            # warm-up: compile + first chunk
    epochs = 3
    base = fused._train_dispatches
    t0 = time.perf_counter()
    hist = fused.fit_epochs(epochs)
    jax.block_until_ready(hist)
    sec = time.perf_counter() - t0
    fused_wps = epochs * cache.n_words / sec
    dispatches_per_epoch = (fused._train_dispatches - base) / epochs
    assert dispatches_per_epoch <= 1, (
        f"fused skip-gram dispatched {dispatches_per_epoch}/epoch — the "
        "whole-chunk contract is broken")

    # --- row-sharded tables: per-chip bytes on a data=1 x model=2 mesh
    table_bytes = int(np.asarray(fused.syn0).nbytes
                      + np.asarray(fused.syn1neg).nbytes)
    sharded_per_chip = None
    if len(jax.devices()) >= 2 and vocab % 2 == 0:
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, build_mesh
        from deeplearning4j_tpu.parallel.sharding_registry import (
            ShardingRegistry)

        mesh2 = build_mesh(MeshSpec(data=1, model=2),
                           devices=jax.devices()[:2])
        reg = ShardingRegistry.for_embedding_tables(
            {"syn0": fused.syn0, "syn1neg": fused.syn1neg}, mesh2,
            row_shard=True)
        placed = reg.place({"syn0": fused.syn0,
                            "syn1neg": fused.syn1neg})
        sharded_per_chip = int(sum(
            s.data.nbytes for t in placed.values()
            for s in t.addressable_shards) // 2)

    _log(f"word2vec: host {host_wps:,.0f} words/sec, fused "
         f"{fused_wps:,.0f} ({fused_wps / max(host_wps, 1e-9):,.1f}x), "
         f"{dispatches_per_epoch:.2f} dispatches/epoch")
    return {"words_per_sec": round(fused_wps, 1),  # fused = the headline
            "host_words_per_sec": round(host_wps, 1),
            "host_cold_words_per_sec": round(host_cold, 1),
            "fused_words_per_sec": round(fused_wps, 1),
            "speedup_vs_host": round(fused_wps / max(host_wps, 1e-9), 2),
            "dispatches_per_epoch": dispatches_per_epoch,
            "table_bytes": table_bytes,
            "sharded_table_bytes_per_chip": sharded_per_chip,
            "corpus_words": words, "vocab": vocab,
            "cache": cache.describe(),
            "note": "host = pair-emitting Python loop (one dispatch per "
                    "batch, warm jit); fused = whole-epoch lax.scan "
                    "program (1 dispatch/chunk, in-program pair gen)"}


def bench_resnet18():
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import resnet18

    rng = np.random.default_rng(0)
    batch = 256
    x = rng.random((batch, 32, 32, 3), np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    x, y = _dev(x, y)
    net = resnet18(num_classes=10, dtype_policy="bf16").init()
    ds = DataSet(x, y)
    fwd_flops = 1.11e9  # analytic CIFAR ResNet-18 fwd GFLOP/sample
    # the x3 assumes backward ≈ 2x forward (dL/dW + dL/dx) and ignores
    # the updater math — stated here because the compiled cost analysis
    # below counts the REAL program and the divergence field quantifies
    # exactly how much that assumption is off
    analytic_note = ("analytic 1.11 GFLOP fwd/sample x3 "
                     "(assumes bwd = 2x fwd; updater math excluded)")
    prof = _profile_step(
        net._train_step,
        (net.params, net.updater_state, net.net_state,
         jnp.asarray(0, jnp.int32), jnp.asarray(1.0, jnp.float32),
         ((x,), (y,), None, None), net._rng),
        "resnet18_train_step")
    stepwise, fused = _fit_throughput(net, ds, batch, steps=10)
    sps = max(stepwise, fused)
    flops = _flops_entry(3 * fwd_flops, analytic_note, prof, batch)
    per_sample = (flops["analytic_flops"]
                  if flops["cost_analysis_flops"] is None
                  else flops["cost_analysis_flops"])
    tflops = per_sample * sps / 1e12
    tflops_analytic = 3 * fwd_flops * sps / 1e12
    _log(f"resnet18: {sps:,.0f} samples/sec ({stepwise:,.0f} per-step, "
         f"{fused:,.0f} fused), {tflops:.1f} TFLOP/s "
         f"({100 * tflops / _peaks()["bf16_tflops"]:.1f}% MFU, "
         f"flops divergence {flops['flops_divergence_pct']}%)")
    return {"samples_per_sec": round(sps, 1),
            "per_step": round(stepwise, 1), "fused": round(fused, 1),
            "batch": batch,
            "model_tflops": round(tflops, 1),
            "mfu_pct": round(100 * tflops / _peaks()["bf16_tflops"], 1),
            "model_tflops_analytic": round(tflops_analytic, 1),
            "mfu_pct_analytic": round(
                100 * tflops_analytic / _peaks()["bf16_tflops"], 1),
            "flops_source": flops,
            # the profile is of the SINGLE-step program, so the
            # decomposition pairs it with the per-step measured time —
            # not the fused path's (a different program with different
            # dispatch amortization and HBM traffic)
            "cost_model": _cost_model_entry(
                prof, None if stepwise <= 0 else batch / stepwise)}


def bench_infeed():
    """Async device-prefetch overlap vs synchronous feeding on a stream of
    DISTINCT batches (infeed-bound config: the per-batch host→device
    transfer is comparable to the step time)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import (
        AsyncDataSetIterator, ListDataSetIterator)
    from deeplearning4j_tpu.models import mnist_mlp

    rng = np.random.default_rng(0)
    batch, n_batches = 4096, 16
    batches = [DataSet(rng.random((batch, 784), np.float32),
                       np.eye(10, dtype=np.float32)[
                           rng.integers(0, 10, batch)])
               for _ in range(n_batches)]
    net = mnist_mlp(hidden=256, dtype_policy="bf16").init()
    net.fit(batches[0])  # compile
    _sync(net.params)

    def run(make_it):
        it = make_it()
        t0 = time.perf_counter()
        net.fit(it)
        _sync(net.params)
        return batch * n_batches / (time.perf_counter() - t0)

    sync_sps = run(lambda: ListDataSetIterator(batches, batch))
    async_sps = run(lambda: AsyncDataSetIterator(
        ListDataSetIterator(batches, batch), queue_size=4,
        device_prefetch=True))
    _log(f"infeed: {sync_sps:,.0f} samples/sec sync, "
         f"{async_sps:,.0f} async-prefetch "
         f"({async_sps / sync_sps:.2f}x)")
    return {"sync_samples_per_sec": round(sync_sps, 1),
            "async_prefetch_samples_per_sec": round(async_sps, 1),
            "overlap_speedup": round(async_sps / sync_sps, 2),
            "batch": batch, "n_batches": n_batches}


def bench_epoch():
    """Epoch pipeline: HBM-cached whole-epoch fusion (fit_epochs) vs the
    streaming per-step path on the same multi-batch dataset. Reports
    samples/sec both ways plus MEASURED train-program dispatches per epoch
    — the fused path must show exactly 1 (chunk = 1 epoch) vs N for
    streaming, and the fully-fused variant (all epochs in one program)
    amortizes even that."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import mnist_mlp
    from deeplearning4j_tpu.perf.epoch_cache import DeviceDataSetCache

    rng = np.random.default_rng(0)
    batch, n_batches, epochs = 2048, 16, 5
    ds = DataSet(rng.random((batch * n_batches, 784), np.float32),
                 np.eye(10, dtype=np.float32)[
                     rng.integers(0, 10, batch * n_batches)])
    total = batch * n_batches

    budget_check = {}

    def run_cached(chunk):
        net = mnist_mlp(hidden=256, dtype_policy="bf16").init()
        cache = DeviceDataSetCache.build(ListDataSetIterator(ds, batch))
        assert cache is not None, "bench dataset exceeded DL4J_DEVICE_CACHE_MB"
        if not budget_check:
            # runtime check of the per-shard HBM budget model: the
            # analytic resident bytes the build priced vs what the
            # device actually holds for these stacks
            from deeplearning4j_tpu.monitor.memory import (
                validate_cache_budget)

            budget_check.update(validate_cache_budget(cache))
        # warm the SAME chunk length as the timed run: the fused program
        # is keyed on the epoch_keys shape [k, 2], so a chunk=1 warm-up
        # would leave the k=epochs program to compile inside the timing
        net.fit_epochs(cache, chunk, chunk_epochs=chunk)
        _sync(net.params)
        d0 = net._train_dispatches
        t0 = time.perf_counter()
        net.fit_epochs(cache, epochs, chunk_epochs=chunk)
        _sync(net.params)
        sec = time.perf_counter() - t0
        return (total * epochs / sec,
                (net._train_dispatches - d0) / epochs)

    def run_streaming():
        net = mnist_mlp(hidden=256, dtype_policy="bf16").init()
        it = ListDataSetIterator(ds, batch)
        net.fit(it)  # compile
        _sync(net.params)
        d0 = net._train_dispatches
        t0 = time.perf_counter()
        net.fit(it, num_epochs=epochs)
        _sync(net.params)
        sec = time.perf_counter() - t0
        return (total * epochs / sec,
                (net._train_dispatches - d0) / epochs)

    stream_sps, stream_dpe = run_streaming()
    cached_sps, cached_dpe = run_cached(chunk=1)
    fused_sps, fused_dpe = run_cached(chunk=epochs)
    _log(f"epoch: {cached_sps:,.0f} samples/sec cached-fused "
         f"({cached_dpe:.0f} dispatches/epoch), {fused_sps:,.0f} "
         f"fully-fused ({fused_dpe:.2f}), {stream_sps:,.0f} streaming "
         f"({stream_dpe:.0f}) — {cached_sps / stream_sps:.2f}x")
    return {"cached_samples_per_sec": round(cached_sps, 1),
            "fully_fused_samples_per_sec": round(fused_sps, 1),
            "streaming_samples_per_sec": round(stream_sps, 1),
            "speedup": round(cached_sps / stream_sps, 2),
            "dispatches_per_epoch_cached": round(cached_dpe, 2),
            "dispatches_per_epoch_fully_fused": round(fused_dpe, 2),
            "dispatches_per_epoch_streaming": round(stream_dpe, 2),
            "batch": batch, "n_batches": n_batches, "epochs": epochs,
            "total_samples": total,
            "hbm_budget_check": budget_check or None}


def bench_dp_epoch():
    """Sharded epoch pipeline: whole-epoch fusion over the data mesh
    (ParallelWrapper.fit_epochs). Weak scaling — per-chip batch held
    constant as devices grow — reported as samples/sec/chip, plus the
    invariant that the cached sharded path still makes exactly ONE
    train-program dispatch per epoch chunk at ANY device count (the
    composition PERF.md §Round-8 quantifies). Skips cleanly when only
    one device is visible (the single-chip 'epoch' section covers n=1)."""
    import jax

    n = len(jax.devices())
    if n < 2:
        return {"skipped": f"only {n} device visible; dp_epoch needs >= 2",
                "devices": n}
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import mnist_mlp
    from deeplearning4j_tpu.parallel import ParallelWrapper, build_mesh

    rng = np.random.default_rng(0)
    per_chip, n_batches, epochs = 256, 8, 5
    batch = per_chip * n  # weak scaling: global batch grows with the mesh
    total = batch * n_batches
    ds = DataSet(rng.random((total, 784), np.float32),
                 np.eye(10, dtype=np.float32)[rng.integers(0, 10, total)])
    net = mnist_mlp(hidden=256, dtype_policy="bf16").init()
    wrapper = ParallelWrapper(net, mesh=build_mesh())
    cache = wrapper.build_epoch_cache(ListDataSetIterator(ds, batch))
    if cache is None:
        return {"error": "dataset exceeded the per-shard cache budget",
                "devices": n}
    wrapper.fit_epochs(cache, 1, chunk_epochs=1)  # warm the chunk program
    _sync(net.params)
    d0 = net._train_dispatches
    t0 = time.perf_counter()
    wrapper.fit_epochs(cache, epochs, chunk_epochs=1)
    _sync(net.params)
    sec = time.perf_counter() - t0
    sps = total * epochs / sec
    dpe = (net._train_dispatches - d0) / epochs
    _log(f"dp_epoch: {n} devices, {sps:,.0f} samples/sec "
         f"({sps / n:,.0f}/chip), {dpe:.2f} dispatches/epoch "
         f"(cache sharded {cache.n_shard} ways)")
    return {"devices": n, "global_batch": batch,
            "per_chip_batch": per_chip, "n_batches": n_batches,
            "epochs": epochs,
            "samples_per_sec": round(sps, 1),
            "samples_per_sec_per_chip": round(sps / n, 1),
            "dispatches_per_epoch": round(dpe, 2),
            "cache_n_shard": cache.n_shard,
            "cache_mb_total": round(cache.nbytes / 1024 ** 2, 2)}


def bench_mesh_sweep():
    """DP×TP grid under the sharding registry: the SAME fused epoch
    program launched over each mesh shape. Per shape: dispatches/chunk
    (must stay 1 — the registry composes the axes into ONE GSPMD
    program), steady-state step time, and the per-chip HBM model
    (params + updater state actually resident on the fullest device +
    the cache's per-shard slice). The most-TP shape's step time and
    per-chip HBM are the TRACKED series: TP must shrink per-chip weights
    without breaking whole-epoch fusion. Embeds registry.describe() for
    the record."""
    import jax

    n = len(jax.devices())
    if n < 4:
        return {"skipped": f"only {n} devices visible; mesh_sweep "
                           "needs >= 4", "devices": n}
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import mnist_mlp
    from deeplearning4j_tpu.parallel import build_mesh
    from deeplearning4j_tpu.parallel.mesh import MeshSpec

    rng = np.random.default_rng(0)
    per_chip, n_batches, epochs = 128, 8, 4
    batch = per_chip * n
    total = batch * n_batches
    ds = DataSet(rng.random((total, 784), np.float32),
                 np.eye(10, dtype=np.float32)[rng.integers(0, 10, total)])

    def per_device_mb(trees):
        # bytes on the FULLEST device — replicated leaves count fully
        # on every device, sharded leaves only their local slice
        per = {}
        for tree in trees:
            for leaf in jax.tree_util.tree_leaves(tree):
                for s in getattr(leaf, "addressable_shards", ()):
                    per[s.device.id] = (per.get(s.device.id, 0)
                                        + s.data.nbytes)
        return max(per.values(), default=0) / 1024 ** 2

    shapes = [(n, 1), (n // 2, 2)]
    if n % 4 == 0:
        shapes.append((n // 4, 4))
    grid, describe = [], None
    for dp, tp in shapes:
        net = mnist_mlp(hidden=512).init()
        mesh = build_mesh(MeshSpec(data=dp, model=tp))
        cache = net.build_epoch_cache(
            ListDataSetIterator(ds, batch), mesh=mesh)
        if cache is None:
            grid.append({"mesh": f"{dp}x{tp}",
                         "error": "cache over budget"})
            continue
        t0 = time.perf_counter()
        net.fit_epochs(cache, 1, chunk_epochs=1)  # compile + warm
        _sync(net.params)
        compile_s = time.perf_counter() - t0
        d0 = net._train_dispatches
        t0 = time.perf_counter()
        net.fit_epochs(cache, epochs, chunk_epochs=1)
        _sync(net.params)
        sec = time.perf_counter() - t0
        dpc = (net._train_dispatches - d0) / epochs
        row = {"mesh": f"{dp}x{tp}", "dp": dp, "tp": tp,
               "dispatches_per_chunk": round(dpc, 2),
               "compile_s": round(compile_s, 3),
               "step_ms": round(sec / (epochs * n_batches) * 1e3, 3),
               "samples_per_sec": round(total * epochs / sec, 1),
               "per_chip_weights_mb": round(
                   per_device_mb([net.params, net.updater_state]), 3),
               "per_chip_hbm_mb": round(
                   per_device_mb([net.params, net.updater_state])
                   + cache.nbytes / max(1, cache.n_shard) / 1024 ** 2, 3)}
        grid.append(row)
        describe = net._sharding_registry.describe()
        _log(f"mesh_sweep {row['mesh']}: {row['step_ms']} ms/step, "
             f"{row['dispatches_per_chunk']} dispatches/chunk, "
             f"{row['per_chip_hbm_mb']} MB/chip")
    good = [r for r in grid if "error" not in r]
    if not good:
        return {"devices": n, "grid": grid,
                "error": "no mesh shape fit the cache budget"}
    tp_row = max(good, key=lambda r: r["tp"])
    return {"devices": n, "grid": grid,
            "tp_mesh": tp_row["mesh"],
            "tp_step_ms": tp_row["step_ms"],
            "tp_dispatches_per_chunk": tp_row["dispatches_per_chunk"],
            "tp_per_chip_hbm_mb": tp_row["per_chip_hbm_mb"],
            "registry": describe}


def bench_guard():
    """Self-healing overhead: (1) fused-epoch throughput with the numeric
    sentinel compiled in (DL4J_NAN_GUARD=skip, the default) vs compiled
    out (=off) — the per-step isfinite-on-loss+grads and the lax.cond
    must cost <3%; (2) save_async: how long the host is blocked taking a
    checkpoint (device->host snapshot only) vs the full zip+manifest
    write that hides behind the next chunk's dispatch."""
    import tempfile

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import mnist_mlp
    from deeplearning4j_tpu.parallel.cluster import FaultTolerantTrainer
    from deeplearning4j_tpu.perf.epoch_cache import DeviceDataSetCache

    rng = np.random.default_rng(0)
    batch, n_batches, epochs = 2048, 16, 5
    ds = DataSet(rng.random((batch * n_batches, 784), np.float32),
                 np.eye(10, dtype=np.float32)[
                     rng.integers(0, 10, batch * n_batches)])
    total = batch * n_batches

    def prep(guard):
        net = mnist_mlp(hidden=256, dtype_policy="bf16").init()
        cache = DeviceDataSetCache.build(ListDataSetIterator(ds, batch))
        assert cache is not None, "bench dataset exceeded DL4J_DEVICE_CACHE_MB"
        # chunk_epochs=1 on purpose: the guarded path must be charged
        # for chunked dispatch too (skip defers its trip read, so its
        # chunks pipeline like the unguarded path's — this verifies it)
        net.fit_epochs(cache, epochs, chunk_epochs=1, guard=guard)
        _sync(net.params)  # warm: compile outside the timing
        return net, cache

    def timed(net, cache, guard):
        t0 = time.perf_counter()
        net.fit_epochs(cache, epochs, chunk_epochs=1, guard=guard)
        _sync(net.params)
        return total * epochs / (time.perf_counter() - t0)

    off_net, off_cache = prep("off")
    net, cache = prep("skip")
    # best-of-3, interleaved: host-side timing jitter dwarfs a few-%
    # sentinel delta on a loaded machine, and min-of-N is the standard
    # way to strip it
    off_sps = max(timed(off_net, off_cache, "off") for _ in range(3))
    on_sps = max(timed(net, cache, "skip") for _ in range(3))
    overhead_pct = (off_sps / on_sps - 1.0) * 100.0

    # save_async: blocking time (snapshot) vs hidden write time
    with tempfile.TemporaryDirectory() as d:
        trainer = FaultTolerantTrainer(net, d)
        t0 = time.perf_counter()
        fut = trainer.save_async()
        blocked_ms = (time.perf_counter() - t0) * 1e3
        t1 = time.perf_counter()
        net.fit_epochs(cache, 1, chunk_epochs=1, guard="skip")
        _sync(net.params)
        chunk_ms = (time.perf_counter() - t1) * 1e3
        fut.result()
        write_ms = (time.perf_counter() - t1) * 1e3
        # "hidden" = the next dispatch never waited on the writer: the
        # host was blocked only for the device->host snapshot, a sliver
        # of the background write it overlaps
        hidden = blocked_ms < 0.05 * write_ms

    _log(f"guard: sentinel {on_sps:,.0f} samples/sec vs {off_sps:,.0f} "
         f"unguarded ({overhead_pct:+.2f}% overhead, target <3%); "
         f"save_async blocked host {blocked_ms:.1f} ms, write "
         f"{write_ms:.1f} ms vs next-chunk {chunk_ms:.1f} ms "
         f"({'hidden' if hidden else 'NOT hidden'})")
    return {"guarded_samples_per_sec": round(on_sps, 1),
            "unguarded_samples_per_sec": round(off_sps, 1),
            "sentinel_overhead_pct": round(overhead_pct, 2),
            "overhead_within_target": bool(overhead_pct < 3.0),
            "save_async_blocked_ms": round(blocked_ms, 2),
            "save_async_write_ms": round(write_ms, 2),
            "next_chunk_ms": round(chunk_ms, 2),
            "save_hidden_behind_next_chunk": bool(hidden),
            "batch": batch, "n_batches": n_batches, "epochs": epochs}


def bench_telemetry():
    """Telemetry overhead: fused-epoch throughput with the in-program
    metrics pack compiled in (grad/update/param global-norms + lr scale
    per step, DL4J_TELEMETRY=on stride 1) vs compiled out — the pack's
    budget is <3% like the NaN sentinel's. The run keeps the default
    guard (skip) on BOTH sides so the delta isolates the pack. Also
    reports the exporter round-trip (JSONL metrics record + Prometheus
    textfile per snapshot) and the host cost of draining one chunk's
    [E, N, 4] history."""
    import os
    import tempfile

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import mnist_mlp
    from deeplearning4j_tpu.monitor import metrics
    from deeplearning4j_tpu.monitor.exporters import (
        JsonlExporter, write_prometheus_textfile)
    from deeplearning4j_tpu.perf.epoch_cache import DeviceDataSetCache

    rng = np.random.default_rng(0)
    batch, n_batches, epochs = 2048, 16, 5
    ds = DataSet(rng.random((batch * n_batches, 784), np.float32),
                 np.eye(10, dtype=np.float32)[
                     rng.integers(0, 10, batch * n_batches)])
    total = batch * n_batches

    def prep(telemetry):
        net = mnist_mlp(hidden=256, dtype_policy="bf16").init()
        cache = DeviceDataSetCache.build(ListDataSetIterator(ds, batch))
        assert cache is not None, "bench dataset exceeded DL4J_DEVICE_CACHE_MB"
        net.fit_epochs(cache, epochs, chunk_epochs=1, telemetry=telemetry)
        _sync(net.params)  # warm: compile outside the timing
        return net, cache

    def timed(net, cache, telemetry):
        t0 = time.perf_counter()
        net.fit_epochs(cache, epochs, chunk_epochs=1, telemetry=telemetry)
        _sync(net.params)
        return total * epochs / (time.perf_counter() - t0)

    off_net, off_cache = prep(False)
    on_net, on_cache = prep(1)
    # best-of-3, interleaved: host timing jitter dwarfs a few-% delta
    off_sps = max(timed(off_net, off_cache, False) for _ in range(3))
    on_sps = max(timed(on_net, on_cache, 1) for _ in range(3))
    overhead_pct = (off_sps / on_sps - 1.0) * 100.0

    # the [E, N, 4] history drain: the one host readback a per-chunk
    # metrics consumer pays
    t0 = time.perf_counter()
    hist = np.asarray(on_net._last_metrics)
    drain_ms = (time.perf_counter() - t0) * 1e3
    finite_frac = float(np.isfinite(hist).mean())

    # exporter round-trip on the live registry
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        JsonlExporter(os.path.join(d, "telemetry.jsonl")).write(
            {"kind": "metrics", "metrics": metrics().snapshot()})
        prom = write_prometheus_textfile(
            metrics(), os.path.join(d, "metrics.prom"))
        export_ms = (time.perf_counter() - t0) * 1e3
        prom_bytes = os.path.getsize(prom) if prom else 0

    _log(f"telemetry: {on_sps:,.0f} samples/sec with metrics pack vs "
         f"{off_sps:,.0f} without ({overhead_pct:+.2f}% overhead, target "
         f"<3%); history drain {drain_ms:.1f} ms, exporters "
         f"{export_ms:.1f} ms ({prom_bytes} B prom)")
    return {"pack_samples_per_sec": round(on_sps, 1),
            "no_pack_samples_per_sec": round(off_sps, 1),
            "pack_overhead_pct": round(overhead_pct, 2),
            "overhead_within_target": bool(overhead_pct < 3.0),
            "metrics_history_shape": list(hist.shape),
            "metrics_finite_fraction": round(finite_frac, 4),
            "history_drain_ms": round(drain_ms, 2),
            "exporter_roundtrip_ms": round(export_ms, 2),
            "prometheus_bytes": prom_bytes,
            "batch": batch, "n_batches": n_batches, "epochs": epochs}


def bench_flight():
    """Run-observability overhead: fused-epoch throughput with the
    flight recorder live (DL4J_FLIGHT-equivalent: every chunk boundary,
    span, and ledger transition streaming to the on-disk segment ring)
    vs off — the budget is <3% like the sentinel and the metrics pack.
    The run ledger itself is always on (host-side arithmetic), so the
    delta isolates the recorder. Also reports the ledger's goodput for
    the timed run, the recorder's write stats, and a postmortem round
    trip: the completed run's surviving segments must classify as
    ``clean``."""
    import tempfile

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import mnist_mlp
    from deeplearning4j_tpu.monitor.flight import (
        FlightRecorder, classify_end_state, load_flight_records,
        set_flight)
    from deeplearning4j_tpu.monitor.ledger import run_ledger
    from deeplearning4j_tpu.perf.epoch_cache import DeviceDataSetCache

    rng = np.random.default_rng(0)
    batch, n_batches, epochs = 2048, 16, 5
    ds = DataSet(rng.random((batch * n_batches, 784), np.float32),
                 np.eye(10, dtype=np.float32)[
                     rng.integers(0, 10, batch * n_batches)])
    total = batch * n_batches

    def prep():
        net = mnist_mlp(hidden=256, dtype_policy="bf16").init()
        cache = DeviceDataSetCache.build(ListDataSetIterator(ds, batch))
        assert cache is not None, "bench dataset exceeded DL4J_DEVICE_CACHE_MB"
        net.fit_epochs(cache, epochs, chunk_epochs=1)
        _sync(net.params)  # warm: compile outside the timing
        return net, cache

    def timed(net, cache):
        t0 = time.perf_counter()
        net.fit_epochs(cache, epochs, chunk_epochs=1)
        _sync(net.params)
        return total * epochs / (time.perf_counter() - t0)

    off_net, off_cache = prep()
    on_net, on_cache = prep()
    # best-of-3, interleaved: host timing jitter dwarfs a few-% delta
    off_sps = max(timed(off_net, off_cache) for _ in range(3))
    with tempfile.TemporaryDirectory() as d:
        recorder = FlightRecorder(d)
        set_flight(recorder)
        try:
            on_sps = max(timed(on_net, on_cache) for _ in range(3))
        finally:
            set_flight(None)
            recorder.close()
        records = load_flight_records(d)
        end_state = classify_end_state(records)["end_state"]
    overhead_pct = (off_sps / on_sps - 1.0) * 100.0
    goodput = run_ledger().last_run_goodput()

    _log(f"flight: {on_sps:,.0f} samples/sec recorded vs {off_sps:,.0f} "
         f"unrecorded ({overhead_pct:+.2f}% overhead, target <3%); "
         f"{recorder.records_written} records, "
         f"{recorder.segments_rotated} rotations, goodput "
         f"{goodput if goodput is not None else float('nan'):.1f}%, "
         f"postmortem={end_state}")
    return {"recorded_samples_per_sec": round(on_sps, 1),
            "unrecorded_samples_per_sec": round(off_sps, 1),
            "flight_overhead_pct": round(overhead_pct, 2),
            "overhead_within_target": bool(overhead_pct < 3.0),
            "records_written": recorder.records_written,
            "records_dropped": recorder.records_dropped,
            "segments_rotated": recorder.segments_rotated,
            "goodput_pct": goodput,
            "postmortem_end_state": end_state,
            "batch": batch, "n_batches": n_batches, "epochs": epochs}


def bench_serve():
    """Online serving path: the continuous-batching decode server under
    an open-loop Poisson request stream (ragged prompt/generation
    lengths). Reports p50/p99 request latency, TTFT/TPOT, tokens/sec,
    occupancy, and the compile-flatness evidence: program builds during
    the warmup stream vs after a second ragged stream — the steady-state
    count MUST stay flat (one decode program + one prefill per ladder
    rung, never a compile per request shape). Also reports the persistent
    XLA compilation cache's entry counts (deeplearning4j_tpu/
    compile_cache.py), so a replica's warm boot is checkable from the
    artifact."""
    from deeplearning4j_tpu.compile_cache import compile_cache_stats
    from deeplearning4j_tpu.models.transformer import TransformerLM
    from deeplearning4j_tpu.serving import (
        DecodeServer, poisson_schedule, run_open_loop)

    lm = TransformerLM(vocab_size=512, d_model=128, num_heads=8,
                       num_kv_heads=4, num_layers=2, max_len=512,
                       seed=7, dtype_policy="bf16",
                       pos_encoding="rope").init()
    slots = 8
    server = DecodeServer(lm, slots=slots, max_len=256)

    # warmup stream: cold compiles (decode + every ladder rung the
    # request mix touches) land here
    warm_sched = poisson_schedule(
        16, rate_rps=200.0, vocab_size=512,
        prompt_lens=(8, 16, 24, 48), max_new_tokens=(8, 16), seed=1)
    run_open_loop(server, warm_sched)
    builds_warm = server.engine.program_builds
    compiles_warm = dict(server.stats()["compiles"])

    # measured stream: same shape menu, 4x the requests — zero new
    # programs may appear
    sched = poisson_schedule(
        64, rate_rps=200.0, vocab_size=512,
        prompt_lens=(8, 16, 24, 48), max_new_tokens=(8, 16), seed=2)
    report = run_open_loop(server, sched)
    builds_steady = server.engine.program_builds
    flat = builds_steady == builds_warm

    summary = report.summary()
    stats = server.stats()
    _log(f"serve: {summary['tokens_per_sec']:,.0f} tokens/sec, "
         f"p50 {summary['p50_latency_ms']} ms / "
         f"p99 {summary['p99_latency_ms']} ms, TTFT p50 "
         f"{summary['ttft_p50_ms']} ms, occupancy "
         f"{summary['occupancy_mean']}; compiles warm={builds_warm} "
         f"steady={builds_steady} "
         f"({'FLAT' if flat else 'NOT FLAT — recompiling per request?'})")

    return {**summary,
            "slots": slots,
            "kv_pool_bytes": stats["kv_pool_bytes"],
            "compiles_after_warmup": compiles_warm,
            "program_builds_warmup": builds_warm,
            "program_builds_steady": builds_steady,
            "compile_count_flat_after_warmup": bool(flat),
            "compile_cache": compile_cache_stats()}


def bench_serve_fleet():
    """Serve fleet: M in-process replicas behind the routing frontend,
    the same Poisson stream replayed per fleet size. One bench host has
    one backend, so in-process replicas time-slice it — the driver books
    each replica's REAL measured dispatch costs on its own virtual
    timeline (the chip-per-replica deployment model); the scaling number
    therefore measures the fleet layer (routing balance, queue spill,
    admission batching), not host parallelism the machine doesn't have.
    Alongside scaling: p50/p99/TTFT vs the single-replica baseline,
    per-replica busy-time balance, and a failover round — one replica
    killed mid-stream, controller eviction, requeue-with-re-prefill on
    the survivor — reporting recovery time and asserting zero lost
    requests + greedy token identity for every rerouted request."""
    import numpy as np

    from deeplearning4j_tpu.models.transformer import TransformerLM
    from deeplearning4j_tpu.serving import poisson_schedule
    from deeplearning4j_tpu.serving.fleet import (
        FleetController, FleetLoadDriver, FleetRouter, ServeReplica)

    lm = TransformerLM(vocab_size=512, d_model=128, num_heads=8,
                       num_kv_heads=4, num_layers=2, max_len=512,
                       seed=7, dtype_policy="bf16",
                       pos_encoding="rope").init()
    prompt_lens = (8, 16, 24)

    def build_replicas(n):
        reps = [ServeReplica(f"r{i}", lm, slots=8, max_len=256)
                for i in range(n)]
        for r in reps:
            # warm every prompt-ladder rung + the decode program
            # on the main thread, outside the measured virtual replay
            for plen in prompt_lens:
                r.server.submit(np.arange(1, plen + 1, dtype=np.int32), 2)
            r.server.drain()
            r.server.finished.clear()
            r._finished_seen = 0
        return reps

    def schedule(seed=5):
        # saturating on purpose (arrival span << 1-replica busy time):
        # an arrival-limited stream would show flat tokens/sec at every
        # fleet size and measure nothing
        return poisson_schedule(48, rate_rps=2000.0, vocab_size=512,
                                prompt_lens=prompt_lens,
                                max_new_tokens=(16,), seed=seed)

    def run_fleet_once(n):
        reps = build_replicas(n)
        router = FleetRouter(reps)
        driver = FleetLoadDriver(
            router, FleetController(router, None, evict_timeout_s=5.0))
        report = driver.run(schedule())
        s = report.summary()
        busy = driver.busy_seconds()
        vals = list(busy.values())
        s["replicas"] = n
        s["busy_seconds"] = {k: round(v, 4) for k, v in busy.items()}
        # balance = min/max busy time: 1.0 is a perfectly even split
        # (busy_seconds seeds every replica, so a starved one reads 0)
        s["balance"] = (round(min(vals) / max(vals), 4)
                        if len(vals) > 1 and max(vals) > 0 else 1.0)
        s["dispatches"] = {rid: sum(1 for r, _, _ in driver.dispatch_log
                                    if r == rid) for rid in busy}
        return s

    def run_fleet(n, rounds=2):
        # real measured dispatch costs carry single-run wall noise
        # (~10-20% on a busy host); best-of-N is the capability
        # estimate, same-schedule replay keeps it apples-to-apples
        s = max((run_fleet_once(n) for _ in range(rounds)),
                key=lambda r: r["tokens_per_sec"])
        _log(f"serve_fleet[{n}r]: {s['tokens_per_sec']:,.0f} tok/s, "
             f"p50 {s['p50_latency_ms']} ms, TTFT p50 "
             f"{s['ttft_p50_ms']} ms, balance {s['balance']} "
             f"(best of {rounds})")
        return s

    fleet = {n: run_fleet(n) for n in (1, 2, 4)}
    base = fleet[1]["tokens_per_sec"]
    scaling = {n: round(fleet[n]["tokens_per_sec"] / base, 4)
               for n in fleet}
    _log(f"serve_fleet: tokens/sec scaling vs 1 replica: "
         + ", ".join(f"{n}r={scaling[n]}" for n in sorted(scaling)))
    # the clock model books REAL measured dispatch costs: scaling above
    # the replica count is impossible from routing alone and means the
    # host was contended during one of the runs — flag it rather than
    # report an inflated win as clean
    noise_flag = any(scaling[n] > n * 1.1 for n in scaling)
    if noise_flag:
        _log("serve_fleet: WARNING — superlinear scaling measured; the "
             "baseline run's dispatch costs were likely inflated by "
             "host contention (rerun on an idle machine)")

    # ---- failover: kill one of two replicas mid-stream ---------------
    reps = build_replicas(2)
    router = FleetRouter(reps)
    controller = FleetController(router, None, evict_timeout_s=5.0)
    driver = FleetLoadDriver(router, controller)
    report = driver.run(schedule(seed=6), kill_at_s=0.08,
                        kill_replica="r0")
    lost = sum(1 for fr in router.requests if not fr.finished)
    # greedy token identity across the failover: every request's final
    # stream must equal the model's own unassisted greedy decode
    diverged = 0
    for fr in router.requests:
        ref = np.asarray(lm.generate(fr.prompt[None],
                                     fr.max_new_tokens))[0]
        if not np.array_equal(fr.output, ref):
            diverged += 1
    failover_s = (None if driver.failover_done_s is None
                  or driver.kill_time_s is None
                  else round(driver.failover_done_s
                             - driver.kill_time_s, 4))
    evic = controller.eviction_log[0] if controller.eviction_log else {}
    requeued = evic.get("failover", {}).get("victims", 0)
    _log(f"serve_fleet: failover — {requeued} requests requeued, "
         f"{lost} lost, {diverged} diverged, recovery "
         f"{failover_s}s past the kill (detection floor is "
         f"DL4J_SERVE_EVICT_S in deployment; the bench evicts at the "
         f"kill instant)")
    assert lost == 0, f"failover lost {lost} request(s)"
    assert diverged == 0, (
        f"failover broke greedy token identity on {diverged} request(s)")

    return {
        "fleet": {str(n): fleet[n] for n in fleet},
        "fleet_tokens_per_sec": fleet[2]["tokens_per_sec"],
        "single_tokens_per_sec": base,
        "tokens_per_sec_scaling_2r": scaling[2],
        "tokens_per_sec_scaling_4r": scaling[4],
        "scaling_2r_target_met": bool(scaling[2] >= 1.8),
        "scaling_noise_flag": noise_flag,
        "p50_latency_ms_2r": fleet[2]["p50_latency_ms"],
        "p99_latency_ms_2r": fleet[2]["p99_latency_ms"],
        "ttft_p50_ms_2r": fleet[2]["ttft_p50_ms"],
        "balance_2r": fleet[2]["balance"],
        "failover": {
            "requeued": requeued,
            "lost_requests": lost,
            "diverged_requests": diverged,
            "failover_complete_s": failover_s,
            "finished": report.summary()["finished"],
            "eviction_reason": evic.get("reason"),
        },
        "failover_complete_s": failover_s,
        "clock_model": "per-replica virtual timelines over real "
                       "measured dispatch costs (chip-per-replica)",
    }


def bench_eval():
    """Inference/eval path: device-resident confusion accumulation vs the
    host path (per-batch logit readback) on a stream of ragged batches.
    Reports samples/sec both ways plus jit compile counts — the device
    path must show exactly one compile per shape bucket and one host
    transfer per evaluate() call (the PERF.md eval invariants)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import mnist_mlp

    rng = np.random.default_rng(0)
    # seven full batches + a ragged tail: two shape buckets total
    sizes = [4096] * 7 + [1777]
    batches = [DataSet(rng.random((b, 784), np.float32),
                       np.eye(10, dtype=np.float32)[
                           rng.integers(0, 10, b)])
               for b in sizes]
    total = sum(sizes)
    net = mnist_mlp(hidden=256, dtype_policy="bf16").init()

    def run(device):
        t0 = time.perf_counter()
        ev = net.evaluate(batches, device_accumulation=device)
        # evaluate() ends on a host readback either way — already synced
        return total / (time.perf_counter() - t0), ev

    run(True)  # compile both bucket programs
    device_sps, ev_dev = run(True)
    run(False)
    host_sps, ev_host = run(False)
    if abs(ev_dev.accuracy() - ev_host.accuracy()) > 1e-12:
        _log(f"eval: DEVICE/HOST ACCURACY MISMATCH "
             f"{ev_dev.accuracy()} vs {ev_host.accuracy()}")
    readbacks_per_call = net._eval_readbacks / 2  # two device runs above
    _log(f"eval: {device_sps:,.0f} samples/sec device-resident, "
         f"{host_sps:,.0f} host path ({device_sps / host_sps:.2f}x), "
         f"{net._eval_step._cache_size()} compiles for "
         f"{len(set(sizes))} buckets")
    return {"device_samples_per_sec": round(device_sps, 1),
            "host_samples_per_sec": round(host_sps, 1),
            "speedup": round(device_sps / host_sps, 2),
            "eval_compiles": net._eval_step._cache_size(),
            "output_compiles": net._output_fn._cache_size(),
            "host_transfers_per_call": readbacks_per_call,
            "batches": len(sizes), "total_samples": total,
            "accuracy_match": bool(
                abs(ev_dev.accuracy() - ev_host.accuracy()) <= 1e-12)}


def _transformer(t, vocab=8192, d=512, layers=8, heads=8, attn="auto",
                 remat=False, window=None, policy="mixed_bf16"):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    # mixed_bf16 = bf16 forward/backward on a per-step parameter copy
    # with f32 master weights + f32 Adam state (the training default);
    # policy="float32" builds the speedup-probe baseline
    return TransformerLM(vocab_size=vocab, d_model=d, num_heads=heads,
                         num_layers=layers, max_len=t, seed=0,
                         dtype_policy=policy, attn_impl=attn, remat=remat,
                         attn_window=window)


def _transformer_flops_per_token(lm, t):
    n_params_matmul = sum(
        int(np.prod(p.shape)) for blk in lm.params["blocks"]
        for grp in blk.values() for p in grp.values())
    n_params_matmul += lm.d_model * lm.vocab_size  # tied unembedding
    # attention term: avg keys/query is t/2 causal; banded it is the
    # exact causal-window average w·(t-(w-1)/2)/t — queries q < w-1 see
    # only q+1 keys (keeps windowed-config MFU honest: banding REMOVES
    # model FLOPs, and rounding the average UP would flatter the number)
    if lm.attn_window is None or lm.attn_window >= t:
        avg_keys = t / 2
    else:
        w = lm.attn_window
        avg_keys = w * (t - (w - 1) / 2) / t
    return int(6 * n_params_matmul
               + 12 * lm.num_layers * lm.d_model * avg_keys)


def _bench_transformer_cfg(batch, t, steps=10, fused_k=10, attn="auto",
                           remat=False, window=None, policy="mixed_bf16"):
    import jax.numpy as jnp

    lm = _transformer(t, attn=attn, remat=remat, window=window,
                      policy=policy).init()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 8192, (batch, t)), jnp.int32)
    _sync(tokens)
    step = lm.make_train_step()
    prof = _profile_step(
        step, (lm.params, lm.opt_state, tokens, jnp.asarray(0, jnp.int32)),
        f"transformer_b{batch}_t{t}_{attn}")
    sec_step = _time_loop(lambda: lm.fit_batch(tokens, train_step=step, block=False),
                          steps=steps, sync=lambda: lm.params)
    fused_error = None
    try:
        multi = lm.make_multi_train_step(fused_k)
        sec_fused = _time_loop(
            lambda: lm.fit_batch_multi(tokens, multi_step=multi,
                                       k=fused_k, block=False),
            steps=max(2, steps // fused_k), sync=lambda: lm.params
        ) / fused_k
    except Exception as e:
        # the per-step number still stands as the per-step number, but
        # the config is recorded (and the run exits) as failed
        fused_error = _record_failure(
            f"transformer_b{batch}_t{t}_{attn}.fused", e)
        sec_fused = float("inf")
    sec = min(sec_step, sec_fused)
    tps = batch * t / sec
    fpt = _transformer_flops_per_token(lm, t)
    flops = _flops_entry(
        fpt, "analytic 6*N/token + attention term", prof, batch * t)
    per_token = (flops["analytic_flops"]
                 if flops["cost_analysis_flops"] is None
                 else flops["cost_analysis_flops"])
    tflops = per_token * tps / 1e12
    tflops_analytic = fpt * tps / 1e12
    mfu = 100 * tflops / _peaks()["bf16_tflops"]
    return {
        "tokens_per_sec": round(tps, 1),
        "per_step_tokens_per_sec": round(batch * t / sec_step, 1),
        "fused_tokens_per_sec": (
            0.0 if fused_error else round(batch * t / sec_fused, 1)),
        "fused": f"failed: {fused_error}" if fused_error else "ok",
        "batch": batch, "seq_len": t, "remat": remat,
        "attn_impl": lm._attn_impl(t, train=True),
        "dtype_policy": lm.dtype_policy_name,
        "model_tflops": round(tflops, 1), "mfu_pct": round(mfu, 1),
        "model_tflops_analytic": round(tflops_analytic, 1),
        "mfu_pct_analytic": round(
            100 * tflops_analytic / _peaks()["bf16_tflops"], 1),
        "flops_source": flops,
        "cost_model": _cost_model_entry(prof, sec_step),
    }, tps, lm


def bench_transformer(on_progress=None):
    """``on_progress(partial_dict)`` is called after every sub-config so
    the durable sidecar always holds the configs measured so far — a
    kill mid-sweep (this is the longest section) does not lose the
    whole transformer entry."""
    import jax.numpy as jnp

    # batch sweep at t=1024 (the headline config family)
    sweep = {}
    best_tps, best_cfg = 0.0, None

    def progress(**stages):
        if on_progress is not None:
            partial = {"partial": True, "batch_sweep_t1024": dict(sweep)}
            partial.update(stages)
            on_progress(partial)
    # batch sweep on the auto attention path, plus the Pallas flash
    # kernel FORCED at the best-batch config: the flash backward kernels
    # avoid the [b,h,t,t] f32 score-matrix HBM traffic both directions,
    # so flash may win below the auto heuristic's t>=4096 crossover —
    # measure instead of guessing (entries are labeled by attn_impl)
    for label, batch, attn, remat in (("16", 16, "auto", False),
                                      ("32", 32, "auto", False),
                                      ("32_flash", 32, "flash", False),
                                      ("64", 64, "auto", True)):
        try:
            cfg, tps, _ = _bench_transformer_cfg(batch, 1024, attn=attn,
                                                 remat=remat)
            sweep[label] = cfg
            _log(f"transformer b{batch} t1024 ({cfg['attn_impl']}"
                 f"{', remat' if remat else ''}): "
                 f"{cfg['tokens_per_sec']:,.0f} tok/s "
                 f"({cfg['mfu_pct']:.1f}% MFU)")
            if tps > best_tps:
                best_tps, best_cfg = tps, cfg
        except Exception as e:
            sweep[label] = {"error": _record_failure(
                f"transformer_b{batch}_t1024_{attn}", e)}
        progress()

    # long-context config where the Pallas flash kernel engages
    try:
        flash_cfg, _, lm4k = _bench_transformer_cfg(4, 4096, steps=6,
                                                    fused_k=6)
        flash_cfg["note"] = "flash kernel auto-engages at t>=4096"
        _log(f"transformer b4 t4096 ({flash_cfg['attn_impl']}): "
             f"{flash_cfg['tokens_per_sec']:,.0f} tok/s "
             f"({flash_cfg['mfu_pct']:.1f}% MFU)")
    except Exception as e:
        flash_cfg = {"error": _record_failure("transformer_b4_t4096", e)}
    progress(long_context_t4096=flash_cfg)

    # sliding-window at the same long-context shape: the banded flash
    # grid does O(t·window) work instead of O(t²/2) — the recorded
    # tokens/sec ratio vs the full-causal t4096 entry is the artifact
    # evidence for the banded kernels (window=1024 ⇒ ~2x fewer
    # attention FLOPs at t=4096)
    try:
        win_cfg, _, _ = _bench_transformer_cfg(4, 4096, steps=6, fused_k=6,
                                               attn="flash", window=1024)
        win_cfg["note"] = "banded flash grid, attn_window=1024"
        _log(f"transformer b4 t4096 w1024 (flash banded): "
             f"{win_cfg['tokens_per_sec']:,.0f} tok/s "
             f"({win_cfg['mfu_pct']:.1f}% MFU)")
    except Exception as e:
        win_cfg = {"error": _record_failure(
            "transformer_b4_t4096_w1024", e)}
    # mixed-precision speedup probe: the SAME b16 t1024 config under the
    # float32 policy, PER-STEP path vs the sweep entry's PER-STEP number
    # — strictly like-for-like (the best-of-fused tokens/sec would fold
    # dispatch amortization into a dtype claim). The artifact evidence
    # that the bf16 step buys MXU rate, not just smaller buffers (gated
    # as train_step_bf16_speedup, higher is better).
    b16_step_tps = (sweep.get("16") or {}).get(
        "per_step_tokens_per_sec", 0.0) or 0.0
    bf16_speedup = None
    if b16_step_tps:
        try:
            lm32 = _transformer(1024, policy="float32").init()
            step32 = lm32.make_train_step()
            tokens32 = jnp.asarray(np.random.default_rng(0).integers(
                0, 8192, (16, 1024)), jnp.int32)
            sec32 = _time_loop(
                lambda: lm32.fit_batch(tokens32, train_step=step32,
                                       block=False),
                steps=3, sync=lambda: lm32.params)
            tps32 = 16 * 1024 / sec32
            bf16_speedup = round(b16_step_tps / tps32, 2)
            _log(f"transformer f32 per-step baseline: {tps32:,.0f} tok/s "
                 f"→ bf16 step speedup {bf16_speedup:.2f}x")
        except Exception as e:
            _record_failure("transformer_f32_speedup_probe", e)
    progress(long_context_t4096=flash_cfg,
             long_context_t4096_w1024=win_cfg,
             train_step_bf16_speedup=bf16_speedup)

    result = dict(best_cfg or {})
    if best_cfg and best_cfg is sweep.get("32_flash"):
        # headline basis change is explicit, not silent: earlier rounds'
        # headline was best-of-auto; if the forced-flash probe wins, that
        # is the signal to lower the auto crossover in models/transformer
        result["headline_basis"] = (
            "forced attn_impl=flash beat the auto path at t=1024 — "
            "auto-crossover candidate")
    # best_cfg already carries the dual analytic/cost-analysis
    # flops_source block; only fill the legacy string when the whole
    # sweep errored out and there is no per-config block to keep
    result.setdefault("flops_source",
                      "analytic 6*N/token + attention term")
    result["config"] = "d512 L8 H8 v8192 mixed_bf16 (f32 masters)"
    result["batch_sweep_t1024"] = sweep
    result["long_context_t4096"] = flash_cfg
    result["long_context_t4096_w1024"] = win_cfg
    if bf16_speedup is not None:
        result["train_step_bf16_speedup"] = bf16_speedup
    return result


def _device_stamp() -> dict:
    """``jax.devices()`` read in THIS process — the chip belongs to one
    process, so there is no probe child. A platform other than ``tpu`` or
    a device missing from ``PEAKS`` exits non-zero before anything is
    built: this bench never measures a CPU under a device metric's name."""
    import jax

    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    _log(f"backend: {stamp}")
    if stamp["platform"] != "tpu":
        _log(f"bench.py needs a TPU; JAX found platform="
             f"{stamp['platform']!r}")
        raise SystemExit(1)
    _peaks_for(stamp["kind"])
    return stamp


def _refresh_telemetry(extras):
    """(Re)attach the metrics+span summary block AND the compiled-program
    profile block. Called at every flush and on the final result line, so
    EVERY artifact — complete, partial, or error — carries the current
    timeline and every ProgramProfile collected so far (a section that
    dies mid-run still flushes the profiles its programs captured)."""
    try:
        extras["telemetry"] = _telemetry_summary()
    except Exception as e:  # telemetry must never break the bench
        _log(f"telemetry summary failed: {e}")
    try:
        from deeplearning4j_tpu.monitor.profile import (
            profile_enabled, profiles)

        extras["profile"] = {"enabled": profile_enabled(),
                             "programs": profiles().snapshot()}
    except Exception as e:  # profiling must never break the bench
        _log(f"profile snapshot failed: {e}")
    return extras


def _result_line(extras, headline_value):
    return json.dumps({
        "metric": "transformer_lm_1024ctx_train_tokens_per_sec_per_chip",
        "value": headline_value,
        "unit": "tokens/sec",
        "extras": _refresh_telemetry(extras),
    })


PARTIAL_PATH = "bench_partial.json"


def _flush_partial(extras, complete=False):
    """Persist the configs measured so far to a sidecar file after every
    config. The SIGTERM handler below cannot fire while the main thread
    is blocked inside a non-signal-aware PJRT/XLA call, so the sidecar —
    not the handler — is the durable record; the handler covers the
    kill-between-configs case on stdout."""
    try:
        with open(PARTIAL_PATH, "w") as f:
            json.dump({"complete": complete,
                       "extras": _refresh_telemetry(extras)}, f)
    except OSError as e:
        _log(f"partial flush failed: {e}")


def _install_partial_emitter(extras):
    """If the driver's timeout SIGTERMs the bench mid-run, emit the JSON
    line with every config measured so far instead of dying silently —
    a partial record beats no record (a round-4 kill mid-transformer
    lost all seven earlier configs). Restored to SIG_DFL before the
    successful final print so a late TERM can't append a second,
    contradictory line."""
    import signal

    def on_term(signum, frame):
        extras.setdefault(
            "error", f"bench terminated by signal {signum} before "
                     "completion; extras above are the configs that "
                     "finished")
        tf = extras.get("transformer_lm") or {}
        print(_result_line(extras, tf.get("tokens_per_sec")), flush=True)
        import os
        os._exit(1)

    try:
        signal.signal(signal.SIGTERM, on_term)
    except (ValueError, OSError):  # non-main thread / platform quirk
        pass


def _uninstall_partial_emitter():
    import signal

    try:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
    except (ValueError, OSError):
        pass


def main() -> int:
    import os

    from deeplearning4j_tpu.compile_cache import ensure_compile_cache

    # the bench IS the profiling run: capture every fused program's
    # cost/memory analysis + chunk-boundary HBM watermarks unless the
    # caller explicitly opted out (training entrypoints keep the
    # DL4J_PROFILE=0 default — the unwrapped bitwise program)
    os.environ.setdefault("DL4J_PROFILE", "1")
    _FAILURES.clear()
    stamp = _device_stamp()
    extras = {"peak_tflops_bf16_per_chip": _peaks()["bf16_tflops"],
              "peaks_source": _peaks()["source"],
              "device": stamp,
              "compile_cache_dir": ensure_compile_cache()}
    _install_partial_emitter(extras)
    # seed the sidecar NOW: a stale bench_partial.json from a previous
    # run must never masquerade as this run's durable record (the
    # SIGTERM handler can't fire inside a blocked PJRT call)
    _flush_partial(extras)
    # BENCH_ONLY=transformer (or a comma list of section names) skips the
    # other sections. The transformer headline ALWAYS runs (the driver's
    # result line needs it); "transformer" is accepted in the list to
    # mean "just the headline".
    only = {s.strip() for s in os.environ.get("BENCH_ONLY", "").split(",")
            if s.strip()}
    sections = [("gemm", bench_gemm), ("mnist_mlp", bench_mlp),
                ("lenet5", bench_lenet),
                ("char_lstm", bench_char_lstm),
                ("word2vec", bench_word2vec),
                ("resnet18_cifar10", bench_resnet18),
                ("infeed", bench_infeed),
                ("eval", bench_eval),
                ("epoch", bench_epoch),
                ("dp_epoch", bench_dp_epoch),
                ("mesh_sweep", bench_mesh_sweep),
                ("serve", bench_serve),
                ("serve_fleet", bench_serve_fleet),
                ("guard", bench_guard),
                ("telemetry", bench_telemetry),
                ("flight", bench_flight)]
    if only:
        known = {n for n, _ in sections} | {"transformer"}
        unknown = sorted(only - known)
        if unknown:
            _log(f"BENCH_ONLY contains unknown section names {unknown} "
                 f"(known: {sorted(known)}) — they select nothing")
        skipped = [n for n, _ in sections if n not in only]
        sections = [(n, f) for n, f in sections if n in only]
        extras["bench_only"] = sorted(only)
        if skipped:
            _log(f"BENCH_ONLY={sorted(only)}: skipping {skipped}")
    try:
        for name, fn in sections:
            sp = None
            try:
                # the span stamps the section with tracer start/end
                # timestamps; an exception mid-section is recorded on it
                with _tracer().span(f"bench.{name}") as sp:
                    extras[name] = fn()
            except Exception as e:  # later sections still run; exit != 0
                extras[name] = {"error": _record_failure(name, e)}
            if sp is not None and isinstance(extras.get(name), dict):
                extras[name]["section_span"] = {
                    "start_s": round(sp.start_s, 3),
                    "end_s": round(sp.end_s, 3),
                    "wall_s": round(sp.duration_s, 3)}
            # flush on EVERY section outcome — success or exception —
            # so the sidecar is never more than one section stale
            _flush_partial(extras)

        try:
            def tf_progress(partial):
                extras["transformer_lm"] = partial
                _flush_partial(extras)

            with _tracer().span("bench.transformer") as tf_span:
                tf = bench_transformer(on_progress=tf_progress)
            tf["section_span"] = {
                "start_s": round(tf_span.start_s, 3),
                "end_s": round(tf_span.end_s, 3),
                "wall_s": round(tf_span.duration_s, 3)}
            extras["transformer_lm"] = tf
            headline_value = tf.get("tokens_per_sec")
        except Exception as e:
            extras["transformer_lm"] = {
                "error": _record_failure("transformer", e)}
            headline_value = None
    except BaseException as e:
        # anything that escapes the per-section nets (SystemExit,
        # KeyboardInterrupt, MemoryError) still leaves a durable record
        # with the timeline of what ran
        extras.setdefault("error",
                          f"bench aborted: {type(e).__name__}: {e}"[:300])
        _flush_partial(extras)
        raise

    if _FAILURES:
        extras["failed"] = list(_FAILURES)
    _uninstall_partial_emitter()
    _flush_partial(extras, complete=True)
    print(_result_line(extras, headline_value))
    return 1 if _FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
