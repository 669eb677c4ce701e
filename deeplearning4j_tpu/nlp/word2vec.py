"""Word2Vec: batched device-parallel skip-gram / CBOW.

Re-design of models/word2vec/Word2Vec.java:31 + SequenceVectors.java:48 +
learning/impl/elements/SkipGram.java:24 (iterateSample :160 — per-pair
hierarchical-softmax / negative-sampling row updates on shared syn0/syn1
arrays from Hogwild threads).

TPU-first execution model: the host walks the corpus emitting (center,
context) index pairs with word2vec's reduced-window + frequent-word
subsampling; pairs are batched (thousands at a time) and a single jitted
step per batch does:
  gather rows → σ(u·v) objectives (NEG or HS) → sparse updates via
  ``.at[idx].add`` scatter (deterministic duplicate accumulation).
This replaces lock-free racing threads with one deterministic SPMD program —
same objective, device-scale batch parallelism instead of thread parallelism.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nlp.sentence_iterator import SentenceIterator
from deeplearning4j_tpu.nlp.tokenization import (
    DefaultTokenizerFactory,
    TokenizerFactory,
)
from deeplearning4j_tpu.nlp.vocab import (
    Huffman,
    VocabCache,
    build_vocab,
    padded_huffman_paths,
    subsample_keep_prob,
    unigram_table,
)


# ---------------------------------------------------------------------------
# jitted update steps
# ---------------------------------------------------------------------------


def _row_scale(n_rows, idx, weights=None):
    """1/count-per-row scaling for scatter-adds: a row hit k times in one
    batch receives the MEAN of its k per-pair updates rather than the sum.
    Without this, small vocabs (row hit ~B/V times per batch) multiply the
    effective learning rate by the hit count and diverge — the sequential
    reference recomputes σ between pair updates, which bounds step size.

    ``weights`` (optional, same shape as ``idx``) weights the per-row
    counting — the masked fused paths (``nlp/epoch_kernels``, GloVe's
    padded epoch scan) pass their validity mask so pad slots neither
    update a row nor dilute its mean."""
    contrib = (jnp.ones(idx.shape, jnp.float32) if weights is None
               else weights.astype(jnp.float32))
    counts = jnp.zeros((n_rows,), jnp.float32).at[
        idx.reshape(-1)].add(contrib.reshape(-1))
    return 1.0 / jnp.maximum(counts[idx], 1.0)


def _neg_sampling_math(syn0, syn1neg, centers, contexts, negatives, lr):
    """Skip-gram with negative sampling, one batch of pairs (pure math,
    reused by the single-device jitted step and the mesh-sharded step in
    ``nlp/distributed.py``).

    centers/contexts: [B]; negatives: [B, K]; returns updated tables + loss.
    """
    h = syn0[centers]                      # [B, D]
    v_pos = syn1neg[contexts]              # [B, D]
    v_neg = syn1neg[negatives]             # [B, K, D]

    s_pos = jax.nn.sigmoid(jnp.sum(h * v_pos, axis=-1))          # [B]
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, v_neg))   # [B, K]
    loss = -jnp.mean(jnp.log(s_pos + 1e-10)
                     + jnp.sum(jnp.log(1.0 - s_neg + 1e-10), axis=-1))

    g_pos = (s_pos - 1.0) * lr             # [B]
    g_neg = s_neg * lr                     # [B, K]

    grad_h = (g_pos[:, None] * v_pos
              + jnp.einsum("bk,bkd->bd", g_neg, v_neg))          # [B, D]
    sc_c = _row_scale(syn0.shape[0], centers)
    syn0 = syn0.at[centers].add(-grad_h * sc_c[:, None])
    # contexts and negatives both scatter into syn1neg: count them jointly
    joint = jnp.concatenate([contexts[:, None], negatives], axis=1)  # [B,1+K]
    counts1 = jnp.zeros((syn1neg.shape[0],), jnp.float32).at[
        joint.reshape(-1)].add(1.0)
    sc_pos = 1.0 / jnp.maximum(counts1[contexts], 1.0)
    sc_neg = 1.0 / jnp.maximum(counts1[negatives], 1.0)
    syn1neg = syn1neg.at[contexts].add(-(g_pos * sc_pos)[:, None] * h)
    syn1neg = syn1neg.at[negatives.reshape(-1)].add(
        -((g_neg * sc_neg)[..., None] * h[:, None, :]).reshape(-1, h.shape[-1]))
    return syn0, syn1neg, loss


_neg_sampling_step = jax.jit(_neg_sampling_math, donate_argnums=(0, 1))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _hs_step(syn0, syn1, centers, points, codes, mask, lr):
    """Skip-gram with hierarchical softmax.

    points/codes/mask: [B, C] padded Huffman paths (mask 0 on padding).
    Objective per node: label = 1 - code; maximize log σ((1-2·code)·u·v).
    """
    h = syn0[centers]                              # [B, D]
    v = syn1[points]                               # [B, C, D]
    u = jnp.einsum("bd,bcd->bc", h, v)             # [B, C]
    s = jax.nn.sigmoid(u)
    label = 1.0 - codes
    loss = -jnp.sum(mask * jnp.log(jnp.abs(label - jax.nn.sigmoid(-u)) + 1e-10)) \
        / jnp.maximum(jnp.sum(mask), 1.0)
    g = (s - label) * mask * lr                    # [B, C]
    grad_h = jnp.einsum("bc,bcd->bd", g, v)
    sc_c = _row_scale(syn0.shape[0], centers)
    syn0 = syn0.at[centers].add(-grad_h * sc_c[:, None])
    # inner nodes near the root appear in nearly every path: normalize
    counts1 = jnp.zeros((syn1.shape[0],), jnp.float32).at[
        points.reshape(-1)].add(mask.reshape(-1))
    sc_p = 1.0 / jnp.maximum(counts1[points], 1.0)
    syn1 = syn1.at[points.reshape(-1)].add(
        -((g * sc_p)[..., None] * h[:, None, :]).reshape(-1, h.shape[-1]))
    return syn0, syn1, loss


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _cbow_neg_step(syn0, syn1neg, context_idx, context_mask, targets,
                   negatives, lr):
    """CBOW-NEG: mean of context rows predicts the target."""
    ctx = syn0[context_idx]                            # [B, W, D]
    m = context_mask[..., None]
    denom = jnp.maximum(jnp.sum(context_mask, axis=-1, keepdims=True), 1.0)
    h = jnp.sum(ctx * m, axis=1) / denom               # [B, D]
    v_pos = syn1neg[targets]
    v_neg = syn1neg[negatives]
    s_pos = jax.nn.sigmoid(jnp.sum(h * v_pos, axis=-1))
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, v_neg))
    loss = -jnp.mean(jnp.log(s_pos + 1e-10)
                     + jnp.sum(jnp.log(1.0 - s_neg + 1e-10), axis=-1))
    g_pos = (s_pos - 1.0) * lr
    g_neg = s_neg * lr
    grad_h = (g_pos[:, None] * v_pos
              + jnp.einsum("bk,bkd->bd", g_neg, v_neg)) / denom
    # distribute the mean-gradient onto each (unmasked) context row
    counts0 = jnp.zeros((syn0.shape[0],), jnp.float32).at[
        context_idx.reshape(-1)].add(context_mask.reshape(-1))
    sc0 = (1.0 / jnp.maximum(counts0[context_idx], 1.0))[..., None]
    upd = jnp.broadcast_to(grad_h[:, None, :], ctx.shape) * m * sc0
    syn0 = syn0.at[context_idx.reshape(-1)].add(
        -upd.reshape(-1, ctx.shape[-1]))
    joint = jnp.concatenate([targets[:, None], negatives], axis=1)
    counts1 = jnp.zeros((syn1neg.shape[0],), jnp.float32).at[
        joint.reshape(-1)].add(1.0)
    sc_pos = 1.0 / jnp.maximum(counts1[targets], 1.0)
    sc_neg = 1.0 / jnp.maximum(counts1[negatives], 1.0)
    syn1neg = syn1neg.at[targets].add(-(g_pos * sc_pos)[:, None] * h)
    syn1neg = syn1neg.at[negatives.reshape(-1)].add(
        -((g_neg * sc_neg)[..., None] * h[:, None, :]).reshape(-1, h.shape[-1]))
    return syn0, syn1neg, loss


# ---------------------------------------------------------------------------
# Word2Vec
# ---------------------------------------------------------------------------


class Word2Vec:
    class Builder:
        def __init__(self):
            self._kw = {}

        def min_word_frequency(self, v):
            self._kw["min_word_frequency"] = int(v)
            return self

        def layer_size(self, v):
            self._kw["layer_size"] = int(v)
            return self

        def window_size(self, v):
            self._kw["window_size"] = int(v)
            return self

        def negative_sample(self, v):
            self._kw["negative"] = int(v)
            return self

        def use_hierarchic_softmax(self, b: bool):
            self._kw["hierarchic_softmax"] = bool(b)
            return self

        def elements_learning_algorithm(self, name: str):
            # "SkipGram" | "CBOW" (ElementsLearningAlgorithm SPI)
            self._kw["algorithm"] = name.lower()
            return self

        def iterations(self, v):
            self._kw["iterations"] = int(v)
            return self

        def epochs(self, v):
            self._kw["epochs"] = int(v)
            return self

        def learning_rate(self, v):
            self._kw["learning_rate"] = float(v)
            return self

        def min_learning_rate(self, v):
            self._kw["min_learning_rate"] = float(v)
            return self

        def sampling(self, v):
            self._kw["sampling"] = float(v)
            return self

        def batch_size(self, v):
            self._kw["batch_size"] = int(v)
            return self

        def seed(self, v):
            self._kw["seed"] = int(v)
            return self

        def iterate(self, sentence_iterator: SentenceIterator):
            self._kw["sentence_iterator"] = sentence_iterator
            return self

        def tokenizer_factory(self, tf: TokenizerFactory):
            self._kw["tokenizer_factory"] = tf
            return self

        def build(self) -> "Word2Vec":
            return Word2Vec(**self._kw)

    def __init__(self, sentence_iterator: Optional[SentenceIterator] = None,
                 tokenizer_factory: Optional[TokenizerFactory] = None,
                 min_word_frequency: int = 5, layer_size: int = 100,
                 window_size: int = 5, negative: int = 5,
                 hierarchic_softmax: bool = False, algorithm: str = "skipgram",
                 iterations: int = 1, epochs: int = 1,
                 learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4, sampling: float = 0.0,
                 batch_size: int = 16384, seed: int = 42,
                 table_size: int = 100_000):
        self.sentence_iterator = sentence_iterator
        self.tokenizer_factory = tokenizer_factory or DefaultTokenizerFactory()
        self.min_word_frequency = min_word_frequency
        self.layer_size = layer_size
        self.window_size = window_size
        self.negative = negative
        self.hierarchic_softmax = hierarchic_softmax or negative == 0
        self.algorithm = algorithm
        self.iterations = iterations
        self.epochs = epochs
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.sampling = sampling
        self.batch_size = batch_size
        self.seed = seed
        self.table_size = table_size

        self.vocab: Optional[VocabCache] = None
        self.syn0: Optional[jnp.ndarray] = None
        self.syn1: Optional[jnp.ndarray] = None      # HS inner nodes
        self.syn1neg: Optional[jnp.ndarray] = None   # NEG output table
        self._table: Optional[np.ndarray] = None
        self._rng = np.random.default_rng(seed)
        self._norm_cache: Optional[np.ndarray] = None
        # fused-epoch state (nlp/epoch_kernels): chunk-boundary hooks,
        # the compiled-program cache the contract checker walks, and the
        # dispatch counter bench/dryrun assert on
        self.listeners: list = []
        self.iteration_count = 0
        self._train_dispatches = 0
        self._epochs_done = 0
        self._epoch_steps: Dict[tuple, object] = {}
        self._corpus_cache = None
        self._sharding_registry = None

    # ------------------------------------------------------------------
    def _sentences_tokens(self) -> Iterable[List[str]]:
        self.sentence_iterator.reset()
        for sentence in self.sentence_iterator:
            yield self.tokenizer_factory.create(sentence).get_tokens()

    def build_vocab(self):
        self.vocab = build_vocab(self._sentences_tokens(),
                                 self.min_word_frequency)
        if self.hierarchic_softmax:
            Huffman(self.vocab).build()
        else:
            self._table = unigram_table(self.vocab, self.table_size)
        return self

    def reset_weights(self):
        n, d = self.vocab.num_words(), self.layer_size
        key = jax.random.PRNGKey(self.seed)
        # word2vec init: U(-0.5/d, 0.5/d) for syn0, zeros for output tables
        self.syn0 = (jax.random.uniform(key, (n, d), jnp.float32) - 0.5) / d
        if self.hierarchic_softmax:
            self.syn1 = jnp.zeros((max(n - 1, 1), d), jnp.float32)
        else:
            self.syn1neg = jnp.zeros((n, d), jnp.float32)
        return self

    # ------------------------------------------------------------------
    def _corpus_indices(self, subsample: bool = True) -> List[np.ndarray]:
        """Sentences as filtered index arrays with frequent-word
        subsampling (SkipGram's sampling logic). Vectorized: one dict
        lookup per token, then numpy masking — the per-token Python
        branch-work of the original loop dominated profile time.

        ``subsample=False`` keeps frequent words: the fused corpus cache
        (``nlp/epoch_kernels``) drains raw indices and re-rolls the SAME
        ``subsample_keep_prob`` table in-program, per epoch."""
        out = []
        tok2idx = {w.word: w.index for w in self.vocab.vocab_words()}
        keep_prob = None
        if subsample and self.sampling > 0:
            keep_prob = subsample_keep_prob(self.vocab, self.sampling)
        for tokens in self._sentences_tokens():
            if not tokens:
                continue
            idx = np.fromiter((tok2idx.get(t, -1) for t in tokens),
                              np.int32, count=len(tokens))
            idx = idx[idx >= 0]
            if keep_prob is not None and len(idx):
                idx = idx[self._rng.random(len(idx)) < keep_prob[idx]]
            if len(idx) > 1:
                out.append(idx)
        return out

    def _emit_pairs(self, sentences: List[np.ndarray]
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """(center, context) with word2vec's reduced window, emitted with
        O(window) whole-corpus numpy passes instead of per-token Python
        loops: for each offset d, a pair (i, i±d) exists iff both positions
        share a sentence and the center's reduced window b_i >= d."""
        if not sentences:
            return (np.zeros((0,), np.int32), np.zeros((0,), np.int32))
        lens = np.asarray([len(s) for s in sentences])
        words = np.concatenate(sentences)
        sid = np.repeat(np.arange(len(sentences)), lens)
        b = self._rng.integers(1, self.window_size + 1, len(words))
        centers_parts: List[np.ndarray] = []
        contexts_parts: List[np.ndarray] = []
        for d in range(1, self.window_size + 1):
            if d >= len(words):
                break
            same = sid[:-d] == sid[d:]
            m_left = same & (b[:-d] >= d)   # center at i, context at i+d
            m_right = same & (b[d:] >= d)   # center at i+d, context at i
            centers_parts.append(words[:-d][m_left])
            contexts_parts.append(words[d:][m_left])
            centers_parts.append(words[d:][m_right])
            contexts_parts.append(words[:-d][m_right])
        return (np.concatenate(centers_parts).astype(np.int32),
                np.concatenate(contexts_parts).astype(np.int32))

    # ------------------------------------------------------------------
    # fused whole-epoch path (nlp/epoch_kernels) — the sparse sibling of
    # MultiLayerNetwork.fit_epochs
    # ------------------------------------------------------------------
    def build_corpus_cache(self, budget_mb: Optional[float] = None,
                           mesh=None):
        """Stage the corpus on-device for fused training (None over
        budget / empty corpus — callers fall back to the host loop)."""
        from deeplearning4j_tpu.nlp import epoch_kernels

        if self.vocab is None:
            self.build_vocab()
        cache = epoch_kernels.SkipGramCorpusCache.build(
            self, budget_mb=budget_mb, mesh=mesh)
        self._corpus_cache = cache
        return cache

    def _fused_mode(self, mesh) -> str:
        """How the fused program runs on ``mesh``: ``"rows"`` (tables
        row-sharded over ``model`` — GSPMD partitions the same program),
        ``"dp"`` (batch split over ``data`` inside shard_map), or
        ``"single"``."""
        from deeplearning4j_tpu.nlp.epoch_kernels import w2v_row_shard_mode
        from deeplearning4j_tpu.parallel.sharding_registry import (
            model_axis_size,
        )

        if mesh is None:
            return "single"
        tp = model_axis_size(mesh)
        mode = w2v_row_shard_mode()
        if tp > 1 and mode != "0":
            if self.vocab.num_words() % tp == 0:
                return "rows"
            if mode == "1":
                import logging
                logging.getLogger(__name__).warning(
                    "DL4J_W2V_ROW_SHARD=1 but vocab %d does not tile the "
                    "model axis (size %d) — tables stay replicated",
                    self.vocab.num_words(), tp)
        if int(mesh.shape.get("data", 1)) > 1:
            return "dp"
        return "single"

    def _register_tables(self, cache):
        """syn0/syn1neg into PR 17's ShardingRegistry: row-sharded over
        ``model`` when ``_fused_mode`` says so, else explicit-replicated.
        Places the live tables and stamps ``_sharding_registry`` (the
        contract checker's declared-axes source)."""
        from deeplearning4j_tpu.parallel.sharding_registry import (
            ShardingRegistry,
        )

        mesh = cache.mesh
        if mesh is None:
            self._sharding_registry = None
            return None
        mode = self._fused_mode(mesh)
        tables = {"syn0": self.syn0, "syn1neg": self.syn1neg}
        reg = ShardingRegistry.for_embedding_tables(
            tables, mesh, row_shard=(mode == "rows"),
            name=type(self).__name__)
        placed = reg.place(tables)
        self.syn0, self.syn1neg = placed["syn0"], placed["syn1neg"]
        self._sharding_registry = reg
        return reg

    def _skipgram_program(self, cache):
        """The compiled chunk program for ``cache``'s geometry, built
        once and cached in ``_epoch_steps`` (the contract checker and
        profiler walk this dict like the dense networks')."""
        from deeplearning4j_tpu.monitor.profile import ProfiledProgram
        from deeplearning4j_tpu.nlp.epoch_kernels import make_skipgram_chunk

        mode = self._fused_mode(cache.mesh)
        key = (self.vocab.num_words(), self.layer_size, cache.n_batches,
               cache.batch, cache.window, cache.negative, mode,
               cache.n_shard)
        prog = self._epoch_steps.get(key)
        if prog is None:
            prog = ProfiledProgram(
                make_skipgram_chunk(cache, dp=(mode == "dp")),
                name="w2v_epoch_chunk", key=key)
            self._epoch_steps[key] = prog
        return prog

    def _host_fallback(self, num_epochs: int):
        """Host pair-loop fallback for ``fit_epochs`` (HS/CBOW, fused
        disabled, or cache over budget): run ``fit()`` for exactly
        ``num_epochs`` without disturbing the configured schedule."""
        saved = self.epochs
        try:
            self.epochs = num_epochs
            self.fit()
        finally:
            self.epochs = saved
        self._epochs_done += num_epochs
        return None

    def fit_epochs(self, num_epochs: Optional[int] = None, *,
                   cache=None, chunk_epochs: Optional[int] = None,
                   on_chunk=None, mesh=None,
                   budget_mb: Optional[float] = None):
        """Fused whole-epoch training: E epochs × N batches as ONE
        donated program per chunk. Returns the ``[E, N]`` loss history,
        or ``None`` when the host loop ran instead (HS/CBOW corpora,
        ``DL4J_W2V_FUSED=0``, or a cache over the HBM budget — same
        silent-fallback contract as the dense epoch cache)."""
        from deeplearning4j_tpu.nlp import epoch_kernels

        if num_epochs is None:
            num_epochs = self.epochs
        num_epochs = int(num_epochs)
        if num_epochs <= 0:
            return None
        if self.vocab is None:
            self.build_vocab()
        if self.syn0 is None:
            self.reset_weights()
        if (self.hierarchic_softmax or self.algorithm == "cbow"
                or not epoch_kernels.w2v_fused_enabled()):
            return self._host_fallback(num_epochs)
        if cache is None:
            cache = self._corpus_cache
            if cache is None or (mesh is not None
                                 and cache.mesh is not mesh):
                cache = self.build_corpus_cache(budget_mb=budget_mb,
                                                mesh=mesh)
        if cache is None:
            return self._host_fallback(num_epochs)
        self._corpus_cache = cache
        if cache.mesh is not None and self._sharding_registry is None:
            self._register_tables(cache)
        hist = epoch_kernels.drive_skipgram_chunks(
            self, cache, num_epochs, chunk_epochs=chunk_epochs,
            on_chunk=on_chunk)
        self._norm_cache = None
        return hist

    # ------------------------------------------------------------------
    def fit(self) -> "Word2Vec":
        if self.vocab is None:
            self.build_vocab()
        if self.syn0 is None:
            self.reset_weights()
        sentences = self._corpus_indices()
        if self.hierarchic_softmax:
            points_tbl, codes_tbl, mask_tbl = padded_huffman_paths(
                self.vocab)

        total_steps = 0
        planned = max(1, self.epochs * self.iterations)
        for epoch in range(self.epochs):
            for _ in range(self.iterations):
                centers, contexts = self._emit_pairs(sentences)
                order = self._rng.permutation(len(centers))
                centers, contexts = centers[order], contexts[order]
                # tiny corpora: shrink the batch so each epoch still takes
                # several steps (batched mean-updates need step count)
                batch_size = min(self.batch_size, max(32, len(centers) // 8))
                for start in range(0, len(centers), batch_size):
                    frac = total_steps / max(1, planned * max(
                        1, len(centers) // batch_size))
                    lr = max(self.min_learning_rate,
                             self.learning_rate * (1.0 - frac))
                    c = centers[start:start + batch_size]
                    x = contexts[start:start + batch_size]
                    if len(c) < batch_size:
                        # wrap-around pad to the CONSTANT batch shape: one
                        # compiled program per fit (a ragged tail would
                        # recompile); duplicate pairs collapse to a mean
                        # under the per-row scaling, so padding only
                        # re-weights real pairs slightly
                        c = np.resize(c, batch_size)
                        x = np.resize(x, batch_size)
                    if self.hierarchic_softmax:
                        self.syn0, self.syn1, loss = _hs_step(
                            self.syn0, self.syn1, jnp.asarray(c),
                            jnp.asarray(points_tbl[x]),
                            jnp.asarray(codes_tbl[x]),
                            jnp.asarray(mask_tbl[x]), lr)
                    elif self.algorithm == "cbow":
                        # reuse pairs as (target, single-context) CBOW
                        negs = self._sample_negatives(len(c), x)
                        self.syn0, self.syn1neg, loss = _cbow_neg_step(
                            self.syn0, self.syn1neg,
                            jnp.asarray(x[:, None]),
                            jnp.ones((len(x), 1), jnp.float32),
                            jnp.asarray(c), jnp.asarray(negs), lr)
                    else:
                        loss = self._neg_batch(c, x, lr)
                    total_steps += 1
        self._norm_cache = None
        return self

    def _neg_batch(self, c: np.ndarray, x: np.ndarray, lr: float):
        """One NEG skip-gram batch — the seam DistributedWord2Vec overrides
        to shard the batch over a mesh (nlp/distributed.py)."""
        negs = self._sample_negatives(len(c), x)
        self.syn0, self.syn1neg, loss = _neg_sampling_step(
            self.syn0, self.syn1neg, jnp.asarray(c), jnp.asarray(x),
            jnp.asarray(negs), lr)
        return loss

    def _sample_negatives(self, b: int, positives: np.ndarray) -> np.ndarray:
        k = max(1, self.negative)
        draws = self._table[self._rng.integers(0, len(self._table), (b, k))]
        # resample collisions with the positive once (cheap approximation of
        # the reference's redraw loop)
        collide = draws == positives[:, None]
        if collide.any():
            redraws = self._table[self._rng.integers(0, len(self._table),
                                                     collide.sum())]
            draws[collide] = redraws
        return draws.astype(np.int32)

    # ------------------------------------------------------------------
    # lookups (wordvectors/WordVectorsImpl + BasicModelUtils)
    # ------------------------------------------------------------------
    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        idx = self.vocab.index_of(word)
        if idx < 0:
            return None
        return np.asarray(self.syn0[idx])

    def has_word(self, word: str) -> bool:
        return self.vocab is not None and self.vocab.has_token(word)

    def _normed(self) -> np.ndarray:
        if self._norm_cache is None:
            m = np.asarray(self.syn0)
            self._norm_cache = m / (np.linalg.norm(m, axis=1, keepdims=True)
                                    + 1e-12)
        return self._norm_cache

    def similarity(self, w1: str, w2: str) -> float:
        i, j = self.vocab.index_of(w1), self.vocab.index_of(w2)
        if i < 0 or j < 0:
            return float("nan")
        n = self._normed()
        return float(np.dot(n[i], n[j]))

    def words_nearest(self, positive, negative=(), top_n: int = 10
                      ) -> List[str]:
        """Analogy-style nearest words (BasicModelUtils.wordsNearest)."""
        if isinstance(positive, str):
            positive = [positive]
        n = self._normed()
        query = np.zeros(self.layer_size, np.float32)
        exclude = set()
        for w in positive:
            idx = self.vocab.index_of(w)
            if idx >= 0:
                query += n[idx]
                exclude.add(idx)
        for w in negative:
            idx = self.vocab.index_of(w)
            if idx >= 0:
                query -= n[idx]
                exclude.add(idx)
        query /= (np.linalg.norm(query) + 1e-12)
        sims = n @ query
        for idx in exclude:
            sims[idx] = -np.inf
        top = np.argsort(-sims)[:top_n]
        return [self.vocab.word_at_index(int(i)) for i in top]

    def vocab_size(self) -> int:
        return self.vocab.num_words() if self.vocab else 0
