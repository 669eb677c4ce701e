"""Fused whole-epoch skip-gram: the sparse sibling of ``perf/epoch_cache``.

The host pair-loop in ``nlp/word2vec.py`` emits (center, context) pairs
with numpy and dispatches one jitted step per batch — fine for a warm
CPU, but on a TPU every dispatch costs a host round trip and the emitter
itself runs at Python speed. This module moves the WHOLE training loop
inside one donated XLA program, the same execution model the dense stack
adopted in PRs 3/4:

- :class:`SkipGramCorpusCache` stacks the corpus as bucket-padded
  ``[S, L]`` token/mask arrays resident in HBM, under the same
  ``DL4J_DEVICE_CACHE_MB`` budget the dataset cache obeys (over budget →
  ``None`` → the caller falls back to the host loop, never raises).
- :func:`skipgram_epoch_plan` generates one epoch's pairs IN-PROGRAM:
  reduced-window masks, frequent-word subsampling, unigram-table
  negative draws and the epoch shuffle are all pure functions of one
  ``jax.random`` epoch key. The SAME derivation runs traced inside the
  fused program and eagerly in the equivalence tests, so both paths
  consume identical RNG streams by construction (the ``epoch_schedule``
  idiom — numpy's PCG64 cannot be replayed inside XLA, so the plan IS
  the emitter's distribution, not a re-implementation of its bitstream).
- :func:`make_skipgram_chunk` compiles E epochs x N batches as ONE
  ``lax.scan`` program per chunk (syn0/syn1neg donated, ``[E, N]`` loss
  history). Data parallelism wraps the whole program in ``shard_map``:
  each device updates its slice of every batch, per-pair gradients are
  segment-summed into table deltas locally and all-reduced with one
  ``psum`` over ``data`` — numerically the single-device scatter-add up
  to summation order (the DP-vs-1-device 1e-6 contract). Row-sharded
  tables (``model`` axis, for vocabularies beyond one chip) reuse the
  SAME program under GSPMD: the registry places ``P('model', None)``
  tables and XLA partitions the gathers/scatters.
- :func:`drive_skipgram_chunks` is the host-side chunk driver — the
  lighter sibling of ``drive_epoch_chunks`` (word2vec carries no
  updater/net state): per-chunk tracer spans, ledger windows, watchdog
  deadline, listener + preemption hooks, and the dispatch counter the
  bench asserts on.

Per-epoch keys derive from ``fold_in(base, absolute_epoch)`` — not a
split-per-chunk chain — so a run resumed mid-way (``fit_epochs(2)``
twice vs ``fit_epochs(4)``) consumes the identical key stream
regardless of chunk boundaries.
"""

from __future__ import annotations

import functools
import logging
import os
import time
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.analysis.annotations import traced
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS
from deeplearning4j_tpu.perf.bucketing import bucket_size
from deeplearning4j_tpu.perf.epoch_cache import (
    _traced_build,
    cache_budget_mb,
    chunk_deadline_s,
)

logger = logging.getLogger(__name__)

__all__ = [
    "SkipGramCorpusCache",
    "skipgram_pair_plan",
    "skipgram_negatives",
    "skipgram_epoch_plan",
    "make_skipgram_chunk",
    "drive_skipgram_chunks",
    "w2v_fused_enabled",
    "w2v_row_shard_mode",
]


# ---------------------------------------------------------------------------
# env knobs (docs/env.md)
# ---------------------------------------------------------------------------
def w2v_fused_enabled() -> bool:
    """``DL4J_W2V_FUSED=0`` disables the fused path: ``fit_epochs`` runs
    the host pair-loop instead (the numerics-debugging escape hatch, like
    ``DL4J_DISABLE_BUCKETING`` for shapes)."""
    return os.environ.get("DL4J_W2V_FUSED", "1") != "0"


def w2v_row_shard_mode() -> str:
    """``DL4J_W2V_ROW_SHARD``: ``auto`` (default — row-shard the tables
    over ``model`` whenever the mesh carries that axis and the vocab
    tiles it), ``1`` (same, but warn when it cannot apply), ``0`` (never:
    tables stay replicated, DP only)."""
    return os.environ.get("DL4J_W2V_ROW_SHARD", "auto").strip() or "auto"


# ---------------------------------------------------------------------------
# in-program pair generation (the RNG-replay equivalence surface)
# ---------------------------------------------------------------------------
@traced
def skipgram_pair_plan(pair_key, tokens, mask, keep_prob, window: int):
    """One epoch's pair candidates from the ``[S, L]`` corpus stacks.

    Pure function of ``pair_key`` — runs traced inside the fused chunk
    program AND eagerly in tests/references, so both consume the same
    stream. Replays the host emitter's distribution: a pair (i, i±d)
    exists iff both positions survive subsampling, share a sentence
    (``mask``), and the CENTER's reduced window ``b >= d`` (word2vec's
    per-position ``b ~ U{1..window}``).

    Returns ``(centers, contexts, valid)``, each flat ``[P]`` with
    ``P = S * Σ_d 2(L-d)`` — a static shape; invalid slots carry
    ``valid=0`` and clamped-to-vocab indices the masked updater ignores.
    """
    k_keep, k_win = jax.random.split(pair_key)
    keep = (mask > 0) & (jax.random.uniform(k_keep, tokens.shape)
                         < keep_prob[tokens])
    b = jax.random.randint(k_win, tokens.shape, 1, window + 1)
    centers: List[jnp.ndarray] = []
    contexts: List[jnp.ndarray] = []
    valid: List[jnp.ndarray] = []
    length = int(tokens.shape[1])
    for d in range(1, window + 1):
        if d >= length:
            break
        pair_ok = keep[:, :-d] & keep[:, d:]
        # center at i, context at i+d
        centers.append(tokens[:, :-d])
        contexts.append(tokens[:, d:])
        valid.append(pair_ok & (b[:, :-d] >= d))
        # center at i+d, context at i
        centers.append(tokens[:, d:])
        contexts.append(tokens[:, :-d])
        valid.append(pair_ok & (b[:, d:] >= d))
    return (jnp.concatenate([c.reshape(-1) for c in centers]),
            jnp.concatenate([c.reshape(-1) for c in contexts]),
            jnp.concatenate([v.reshape(-1) for v in valid])
            .astype(jnp.float32))


@traced
def skipgram_negatives(neg_key, contexts, table, k: int):
    """``[P, k]`` unigram-table negative draws with ONE in-program
    collision redraw against the positive — the same cheap approximation
    of the reference's redraw loop the host ``_sample_negatives`` uses,
    expressed as a pure function of ``neg_key``."""
    k1, k2 = jax.random.split(neg_key)
    shape = (contexts.shape[0], k)
    draws = table[jax.random.randint(k1, shape, 0, table.shape[0])]
    redraws = table[jax.random.randint(k2, shape, 0, table.shape[0])]
    return jnp.where(draws == contexts[:, None], redraws, draws)


@traced
def skipgram_epoch_plan(epoch_key, tokens, mask, keep_prob, table,
                        window: int, negative: int, n_batches: int,
                        batch: int):
    """One epoch's full batch plan: pair candidates → pad to ``N*B``
    (pad slots ``valid=0``) → epoch shuffle → negative draws, reshaped
    to the ``[N, B]`` layout the batch scan consumes."""
    k_pairs, k_neg, k_perm = jax.random.split(epoch_key, 3)
    centers, contexts, valid = skipgram_pair_plan(
        k_pairs, tokens, mask, keep_prob, window)
    total = n_batches * batch
    pad = total - centers.shape[0]
    centers = jnp.pad(centers, (0, pad))
    contexts = jnp.pad(contexts, (0, pad))
    valid = jnp.pad(valid, (0, pad))
    order = jax.random.permutation(k_perm, total)
    centers = centers[order]
    contexts = contexts[order]
    valid = valid[order]
    negatives = skipgram_negatives(k_neg, contexts, table, negative)
    return (centers.reshape(n_batches, batch),
            contexts.reshape(n_batches, batch),
            valid.reshape(n_batches, batch),
            negatives.reshape(n_batches, batch, negative))


# ---------------------------------------------------------------------------
# the masked segment-sum NEG updater
# ---------------------------------------------------------------------------
def _neg_epoch_math(syn0, syn1neg, centers, contexts, valid, negatives,
                    lr, axis: Optional[str] = None):
    """Masked skip-gram NEG update as table DELTAS: per-pair gradients
    are segment-summed (mean-normalized per row, ``_row_scale`` weighted
    by ``valid`` so pad slots neither update nor dilute) into sparse
    deltas, then applied. Under ``axis`` (the DP path inside
    ``shard_map``) the row counts AND the deltas all-reduce over the
    mesh axis — the summation the single-device scatter-add performs,
    split across devices."""
    h = syn0[centers]                                        # [B, D]
    v_pos = syn1neg[contexts]                                # [B, D]
    v_neg = syn1neg[negatives]                               # [B, K, D]
    s_pos = jax.nn.sigmoid(jnp.sum(h * v_pos, axis=-1))
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, v_neg))
    per_pair = -(jnp.log(s_pos + 1e-10)
                 + jnp.sum(jnp.log(1.0 - s_neg + 1e-10), axis=-1)) * valid
    loss_sum = jnp.sum(per_pair)
    n_valid = jnp.sum(valid)

    g_pos = (s_pos - 1.0) * lr * valid                       # [B]
    g_neg = s_neg * lr * valid[:, None]                      # [B, K]
    grad_h = (g_pos[:, None] * v_pos
              + jnp.einsum("bk,bkd->bd", g_neg, v_neg))      # [B, D]

    counts0 = jnp.zeros((syn0.shape[0],), jnp.float32).at[
        centers].add(valid)
    joint = jnp.concatenate([contexts[:, None], negatives], axis=1)
    jweights = jnp.concatenate(
        [valid[:, None], jnp.broadcast_to(valid[:, None], negatives.shape)],
        axis=1)
    counts1 = jnp.zeros((syn1neg.shape[0],), jnp.float32).at[
        joint.reshape(-1)].add(jweights.reshape(-1))
    if axis is not None:
        loss_sum = jax.lax.psum(loss_sum, axis)
        n_valid = jax.lax.psum(n_valid, axis)
        counts0 = jax.lax.psum(counts0, axis)
        counts1 = jax.lax.psum(counts1, axis)
    loss = loss_sum / jnp.maximum(n_valid, 1.0)

    # g_* already carry valid; the scale only mean-normalizes per row
    sc_c = 1.0 / jnp.maximum(counts0[centers], 1.0)
    d0 = jnp.zeros_like(syn0).at[centers].add(-grad_h * sc_c[:, None])
    sc_pos = 1.0 / jnp.maximum(counts1[contexts], 1.0)
    sc_neg = 1.0 / jnp.maximum(counts1[negatives], 1.0)
    d1 = jnp.zeros_like(syn1neg).at[contexts].add(
        -(g_pos * sc_pos)[:, None] * h)
    d1 = d1.at[negatives.reshape(-1)].add(
        -((g_neg * sc_neg)[..., None] * h[:, None, :])
        .reshape(-1, h.shape[-1]))
    if axis is not None:
        d0 = jax.lax.psum(d0, axis)
        d1 = jax.lax.psum(d1, axis)
    return syn0 + d0, syn1neg + d1, loss


@traced
def _neg_epoch_impl(syn0, syn1neg, centers, contexts, valid, negatives, lr):
    """Single-device masked NEG step (the equivalence tests' eager
    reference applies this per batch against the fused program)."""
    return _neg_epoch_math(syn0, syn1neg, centers, contexts, valid,
                           negatives, lr, axis=None)


# ---------------------------------------------------------------------------
# the fused chunk program
# ---------------------------------------------------------------------------
def make_skipgram_chunk(cache: "SkipGramCorpusCache", *, dp: bool):
    """ONE donated program running E epochs x N batches:
    ``(syn0, syn1neg, it0, lr0, min_lr, planned, tokens, mask,
    keep_prob, table, epoch_keys[E]) -> (syn0, syn1neg, hist[E, N])``.

    ``dp=True`` wraps the WHOLE program in ``shard_map`` over ``data``:
    the epoch plan is computed replicated (cheap, identical per device —
    same keys), each device slices its ``B/n_shard`` of every batch via
    ``axis_index``, and the masked updater all-reduces counts + deltas.
    Row-sharded tables need no wrapper at all — the same ``dp=False``
    program partitions under GSPMD from the registry's placements."""
    return _make_skipgram_chunk(cache.window, cache.negative,
                                cache.n_batches, cache.batch,
                                cache.n_shard if dp else 1,
                                cache.mesh if dp else None, dp)


@functools.lru_cache(maxsize=32)
def _make_skipgram_chunk(window: int, negative: int, n_batches: int,
                         batch: int, n_shard: int, mesh, dp: bool):
    # module-level memo keyed on the hashable statics the closure bakes
    # in: two Word2Vec instances with the same corpus geometry (every
    # equivalence test's reference-vs-candidate pair, a rebuilt model
    # after preemption) share ONE jit — identical avals reuse the
    # compiled executable instead of re-tracing per instance.
    local_b = batch // max(1, n_shard)
    axis = DATA_AXIS if dp else None

    def _w2v_chunk_impl(syn0, syn1neg, it0, lr0, min_lr, planned,
                        tokens, mask, keep_prob, table, epoch_keys):
        def epoch_body(carry, ekey):
            s0, s1, it = carry
            cen, ctx, val, neg = skipgram_epoch_plan(
                ekey, tokens, mask, keep_prob, table, window, negative,
                n_batches, batch)
            if axis is not None:
                shard = jax.lax.axis_index(axis)
                cen = jnp.take(cen.reshape(n_batches, n_shard, local_b),
                               shard, axis=1)
                ctx = jnp.take(ctx.reshape(n_batches, n_shard, local_b),
                               shard, axis=1)
                val = jnp.take(val.reshape(n_batches, n_shard, local_b),
                               shard, axis=1)
                neg = jnp.take(
                    neg.reshape(n_batches, n_shard, local_b, negative),
                    shard, axis=1)

            def batch_body(c, xs):
                b_s0, b_s1, b_it = c
                lr = jnp.maximum(min_lr, lr0 * (1.0 - b_it / planned))
                b_s0, b_s1, loss = _neg_epoch_math(
                    b_s0, b_s1, xs[0], xs[1], xs[2], xs[3], lr, axis=axis)
                return (b_s0, b_s1, b_it + 1.0), loss

            (s0, s1, it), losses = jax.lax.scan(
                batch_body, (s0, s1, it), (cen, ctx, val, neg))
            return (s0, s1, it), losses

        (syn0, syn1neg, _), hist = jax.lax.scan(
            epoch_body, (syn0, syn1neg, it0), epoch_keys)
        return syn0, syn1neg, hist

    if dp:
        repl = (P(),) * 11
        fn = shard_map(_w2v_chunk_impl, mesh=mesh, in_specs=repl,
                       out_specs=(P(), P(), P()))
    else:
        fn = _w2v_chunk_impl
    return jax.jit(fn, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# the device-resident corpus cache
# ---------------------------------------------------------------------------
class SkipGramCorpusCache:
    """The corpus as HBM-resident ``[S, L]`` token/mask stacks plus the
    vocab-derived tables the in-program pair generator consumes
    (``keep_prob[V]``, the unigram ``table[T]``).

    ``build`` drains the iterator once (NO host subsampling — that moved
    in-program), bucket-pads sentence length up the shared power-of-two
    ladder, prices residents + the per-epoch plan workspace against
    ``DL4J_DEVICE_CACHE_MB``, and returns ``None`` over budget (the
    caller streams through the host loop instead, exactly the
    ``DeviceDataSetCache`` contract)."""

    def __init__(self, *, tokens, mask, keep_prob, table, n_batches: int,
                 batch: int, n_pairs: int, n_words: int, window: int,
                 negative: int, mesh, n_shard: int, nbytes: int):
        self.tokens = tokens
        self.mask = mask
        self.keep_prob = keep_prob
        self.table = table
        self.n_batches = n_batches
        self.batch = batch
        self.n_pairs = n_pairs
        self.n_words = n_words
        self.n_sentences = int(tokens.shape[0])
        self.window = window
        self.negative = negative
        self.mesh = mesh
        self.n_shard = n_shard
        self.nbytes = nbytes

    @classmethod
    def build(cls, w2v, *, budget_mb: Optional[float] = None,
              mesh=None, buckets: Optional[Sequence[int]] = None,
              batch: Optional[int] = None
              ) -> Optional["SkipGramCorpusCache"]:
        """Build under budget, with the shared ``cache.build`` tracer
        span + counter; ``None`` on fallback, never raises."""
        if batch is not None:
            return cls._build(w2v, budget_mb=budget_mb, buckets=buckets,
                              mesh=mesh, accum_steps=None, batch=batch)
        return _traced_build(cls, w2v, budget_mb, buckets, mesh, None)

    @classmethod
    def _build(cls, w2v, *, budget_mb=None, buckets=None, mesh=None,
               accum_steps=None, batch: Optional[int] = None
               ) -> Optional["SkipGramCorpusCache"]:
        # accum_steps is the dense caches' gradient-accumulation knob —
        # meaningless for the sparse updater, accepted for _traced_build
        del accum_steps
        from deeplearning4j_tpu.nlp.vocab import subsample_keep_prob

        sentences = w2v._corpus_indices(subsample=False)
        if not sentences:
            logger.info("w2v corpus cache: empty corpus — host fallback")
            return None
        window = int(w2v.window_size)
        negative = max(1, int(w2v.negative))
        length = bucket_size(max(len(s) for s in sentences),
                             buckets=buckets)
        s_count = len(sentences)
        tokens = np.zeros((s_count, length), np.int32)
        mask = np.zeros((s_count, length), np.float32)
        for i, s in enumerate(sentences):
            tokens[i, :len(s)] = s
            mask[i, :len(s)] = 1.0
        n_words = int(mask.sum())
        keep = subsample_keep_prob(w2v.vocab, w2v.sampling)
        table = np.asarray(w2v._table, np.int32)

        n_pairs = s_count * sum(
            2 * (length - d) for d in range(1, window + 1) if d < length)
        if n_pairs <= 0:
            logger.info("w2v corpus cache: no pair capacity (sentences "
                        "of length 1) — host fallback")
            return None
        n_shard = 1
        if mesh is not None:
            n_shard = max(1, int(mesh.shape.get(DATA_AXIS, 1)))
        if batch is None:
            # tiny corpora shrink the batch so each epoch still takes
            # several mean-normalized steps (mirrors the host loop)
            batch = min(int(w2v.batch_size), max(32, n_pairs // 8))
        # round up to a multiple of 8 (and of n_shard): every power-of-two
        # data axis up to 8 then yields the SAME batch for the same corpus,
        # so the mesh run's single-device reference hits the memoized
        # program instead of compiling a one-off geometry
        mult = 8 if n_shard in (1, 2, 4, 8) else 8 * n_shard
        batch = max(mult, int(batch))
        batch += (-batch) % mult
        n_batches = -(-n_pairs // batch)
        total = n_batches * batch

        resident = (tokens.nbytes + mask.nbytes + keep.nbytes
                    + table.nbytes)
        # the per-epoch plan (pairs + shuffle + negatives) lives in HBM
        # while the chunk runs — price it honestly, not just residents
        workspace = total * 4 * (4 + negative)
        budget = (cache_budget_mb() if budget_mb is None
                  else float(budget_mb))
        if (resident + workspace) / 1024 ** 2 > budget:
            logger.info(
                "w2v corpus cache over budget: %.1f MB resident + %.1f "
                "MB plan workspace > %.1f MB — host-loop fallback",
                resident / 1024 ** 2, workspace / 1024 ** 2, budget)
            return None

        if mesh is None:
            put = jax.device_put
        else:
            from deeplearning4j_tpu.parallel.sharding_registry import (
                replicated_sharding)

            sharding = replicated_sharding(mesh)

            def put(a):
                return jax.device_put(a, sharding)

        return cls(tokens=put(tokens), mask=put(mask),
                   keep_prob=put(keep), table=put(table),
                   n_batches=int(n_batches), batch=int(batch),
                   n_pairs=int(n_pairs), n_words=n_words, window=window,
                   negative=negative, mesh=mesh, n_shard=n_shard,
                   nbytes=resident + workspace)

    def describe(self) -> dict:
        return {
            "sentences": self.n_sentences,
            "bucket_len": int(self.tokens.shape[1]),
            "words": self.n_words,
            "pair_capacity": self.n_pairs,
            "n_batches": self.n_batches,
            "batch": self.batch,
            "mb": round(self.nbytes / 1024 ** 2, 3),
            "n_shard": self.n_shard,
        }


# ---------------------------------------------------------------------------
# host-side chunk driver
# ---------------------------------------------------------------------------
def epoch_keys_for(seed: int, start: int, count: int):
    """``[count]`` per-epoch keys: ``fold_in(base(seed), absolute_epoch)``.
    Keyed by ABSOLUTE epoch index (not a split chain), so chunk
    boundaries and resume points never change the stream — epoch 3's key
    is epoch 3's key whether it runs in chunk one or after a restart."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), 0x57A9)
    return jax.vmap(lambda e: jax.random.fold_in(base, e))(
        jnp.arange(start, start + count))


def drive_skipgram_chunks(w2v, cache: SkipGramCorpusCache,
                          num_epochs: int,
                          chunk_epochs: Optional[int] = None,
                          on_chunk=None):
    """Run ``num_epochs`` fused epochs in chunks of ``chunk_epochs``
    (default: whole run without listeners, 1 with them — the dense
    driver's rule). One dispatch per chunk, counter-asserted by bench
    and dryrun via ``w2v._train_dispatches``.

    The telemetry/robustness bus matches ``drive_epoch_chunks``: ledger
    run/chunk windows, ``epoch.chunk`` tracer spans + dispatch counter,
    a ``StepWatchdog`` scaled to the chunk's step count, the
    ``epoch.chunk`` fault point, listener ``chunk_done`` firing, and an
    ``on_chunk(epochs_done) -> bool`` preemption hook. When a heartbeat
    monitor is attached (``DistributedWord2Vec.attach_heartbeat``) each
    chunk also pays ONE scalar readback to post honest words/sec + loss
    payloads — unattached runs stay sync-free."""
    from deeplearning4j_tpu.monitor import record_counter, tracer
    from deeplearning4j_tpu.monitor.ledger import (
        ledger_chunk_done,
        ledger_chunk_start,
        ledger_run_end,
        ledger_run_start,
    )
    from deeplearning4j_tpu.resilience import faults
    from deeplearning4j_tpu.resilience.watchdog import StepWatchdog

    if chunk_epochs is None:
        chunk_epochs = 1 if getattr(w2v, "listeners", None) else num_epochs
    chunk_epochs = max(1, min(int(chunk_epochs), num_epochs))
    model_name = type(w2v).__name__
    prog = w2v._skipgram_program(cache)
    lr0 = jnp.asarray(w2v.learning_rate, jnp.float32)
    min_lr = jnp.asarray(w2v.min_learning_rate, jnp.float32)
    # the decay horizon is the CONFIGURED epochs (builder), independent
    # of this call's num_epochs — a resumed run continues the same
    # schedule (split runs match the one-shot run exactly)
    planned = jnp.asarray(
        max(1, w2v.epochs) * cache.n_batches, jnp.float32)
    history = []
    done = 0
    stopped = False
    run_error = None
    watchdog = StepWatchdog(
        chunk_deadline_s(chunk_epochs * cache.n_batches))
    w2v._chunk_watchdog = watchdog
    ledger_run_start(model=model_name, epochs=num_epochs,
                     steps=num_epochs * cache.n_batches,
                     chunk_epochs=chunk_epochs, guard="off")
    try:
        with watchdog:
            while done < num_epochs:
                k = min(chunk_epochs, num_epochs - done)
                faults.fault_point("epoch.chunk")
                e0 = w2v._epochs_done
                keys = epoch_keys_for(w2v.seed, e0, k)
                it0 = jnp.asarray(w2v.iteration_count, jnp.float32)
                ledger_chunk_start(model=model_name, epoch0=e0, epochs=k)
                t0 = time.perf_counter()
                with tracer().span("epoch.chunk", model=model_name,
                                   epochs=k, steps=k * cache.n_batches,
                                   epoch0=e0):
                    w2v.syn0, w2v.syn1neg, hist = prog(
                        w2v.syn0, w2v.syn1neg, it0, lr0, min_lr, planned,
                        cache.tokens, cache.mask, cache.keep_prob,
                        cache.table, keys)
                watchdog.beat()
                ledger_chunk_done(model=model_name, epoch0=e0, epochs=k)
                w2v._train_dispatches += 1
                record_counter("train_chunk_dispatches_total",
                               model=model_name)
                w2v.iteration_count += k * cache.n_batches
                w2v._epochs_done += k
                history.append(hist)
                done += k
                if getattr(w2v, "_heartbeat", None) is not None:
                    # heartbeat-instrumented runs pay one scalar sync per
                    # chunk: the fleet's step_s/words-per-sec must be
                    # completion-honest, not dispatch-latency
                    last = float(np.asarray(hist[-1, -1]))
                    dt = max(time.perf_counter() - t0, 1e-9)
                    w2v._heartbeat_stats = {
                        "step_s": dt / (k * cache.n_batches),
                        "words_per_sec": k * cache.n_words / dt,
                        "last_loss": last,
                        "epochs_done": w2v._epochs_done,
                    }
                for listener in getattr(w2v, "listeners", ()):
                    chunk_cb = getattr(listener, "chunk_done", None)
                    if chunk_cb is not None:
                        chunk_cb(w2v, w2v.iteration_count
                                 - k * cache.n_batches, hist,
                                 metrics=None)
                    else:
                        listener.iteration_done(w2v, w2v.iteration_count)
                if on_chunk is not None and on_chunk(done):
                    stopped = True
                    break
    except BaseException as e:
        run_error = e
        raise
    finally:
        ledger_run_end(
            status=(f"error:{type(run_error).__name__}"
                    if run_error is not None
                    else ("stopped" if stopped else "clean")),
            model=model_name, epochs_done=done)
    if len(history) == 1:
        return history[0]
    return jnp.concatenate(history)
