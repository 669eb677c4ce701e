"""Mesh-parallel word2vec — the dl4j-spark-nlp equivalent.

Re-design of ``dl4j-spark-nlp`` (4,983 LoC: ``spark/models/embeddings/
word2vec/Word2Vec.java`` — RDD sentence pipeline, per-partition
``FirstIterationFunction`` training and accumulator-based ``Word2VecParam``
averaging). The semantics carried over: each partition trains skip-gram
locally on its slice of the pair batch and the resulting tables are
AVERAGED across partitions per step. On TPU the partitions are mesh devices,
the pair batch is sharded over the ``data`` axis with ``shard_map``, the
local update is the exact single-device math (``_neg_sampling_math``), and
the average is a ``psum``-backed ``pmean`` over ICI — replacing the Spark
driver round-trip with one collective inside the compiled step.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.nlp.word2vec import Word2Vec, _neg_sampling_math
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS


def make_sharded_neg_step(mesh: Mesh):
    """Jitted step: tables replicated, pair batch sharded over 'data';
    per-shard local update then cross-shard table averaging (the Spark
    accumulator-mean, as one XLA collective)."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(P(), P(), P()),
    )
    def step(syn0, syn1neg, centers, contexts, negatives, lr):
        s0, s1, loss = _neg_sampling_math(syn0, syn1neg, centers, contexts,
                                          negatives, lr)
        return (jax.lax.pmean(s0, DATA_AXIS),
                jax.lax.pmean(s1, DATA_AXIS),
                jax.lax.pmean(loss, DATA_AXIS))

    return jax.jit(step, donate_argnums=(0, 1))


class DistributedWord2Vec(Word2Vec):
    """Word2Vec whose NEG-skip-gram batches shard across a device mesh.

    Only the hot path (skip-gram + negative sampling, the spark module's
    algorithm) distributes; HS and CBOW fall back to the single-device
    steps. Pair batches are padded to a multiple of the data-parallel
    degree by wrapping around to the batch's own first pairs — duplicates
    collapse to a mean under the per-row scaling, so padding only
    re-weights real pairs slightly instead of injecting fake ones.
    """

    def __init__(self, *args, mesh: Optional[Mesh] = None, **kw):
        super().__init__(*args, **kw)
        if mesh is None:
            from deeplearning4j_tpu.parallel.mesh import build_mesh

            mesh = build_mesh()
        self.mesh = mesh
        self._sharded_step = make_sharded_neg_step(mesh)
        self._heartbeat = None
        self._heartbeat_stats = {}

    @property
    def data_parallelism(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    def fit_epochs(self, num_epochs: Optional[int] = None, *,
                   cache=None, chunk_epochs=None, on_chunk=None,
                   mesh=None, budget_mb=None):
        """Fused epochs on ``self.mesh`` by default — the corpus cache,
        chunk program, and table registry all land on the mesh this
        instance was built for."""
        return super().fit_epochs(
            num_epochs, cache=cache, chunk_epochs=chunk_epochs,
            on_chunk=on_chunk, mesh=self.mesh if mesh is None else mesh,
            budget_mb=budget_mb)

    # ------------------------------------------------------------------
    # fleet wiring: embedding runs look like any other worker
    # ------------------------------------------------------------------
    def attach_heartbeat(self, tracker, worker_id: str,
                         interval_s: float = 5.0):
        """Post words/sec + loss payloads to a cluster state tracker so
        the fleet master tick, straggler flagging, and goodput autopilot
        see this run like any dense worker. The fused chunk driver
        refreshes ``_heartbeat_stats`` once per chunk (one sanctioned
        scalar readback); the monitor thread ships whatever is current.

        Returns the :class:`HeartbeatMonitor` — use it as a context
        manager around training, or call ``start()``/``stop()``."""
        from deeplearning4j_tpu.parallel.cluster import HeartbeatMonitor

        def payload():
            stats = dict(self._heartbeat_stats)
            # the master tick reads step_s/last_loss/goodput_pct; extra
            # keys (words_per_sec, epochs_done) ride along for dashboards
            return stats

        self._heartbeat = HeartbeatMonitor(
            tracker, worker_id, interval_s=interval_s,
            payload_fn=payload)
        return self._heartbeat

    def _neg_batch(self, c: np.ndarray, x: np.ndarray, lr: float):
        c = np.asarray(c, np.int32)
        x = np.asarray(x, np.int32)
        negs = self._sample_negatives(len(c), x)
        dp = self.data_parallelism
        pad = (-len(c)) % dp
        if pad:  # wrap-around padding with the batch's own pairs
            c = np.resize(c, len(c) + pad)
            x = np.resize(x, len(x) + pad)
            negs = np.resize(negs, (negs.shape[0] + pad, negs.shape[1]))
        with self.mesh:
            self.syn0, self.syn1neg, loss = self._sharded_step(
                self.syn0, self.syn1neg, jnp.asarray(c), jnp.asarray(x),
                jnp.asarray(negs), jnp.asarray(lr, jnp.float32))
        return loss
