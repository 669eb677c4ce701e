"""Hot-path annotations consumed by dl4j-lint (stdlib-only, zero cost).

The fused training pipeline stakes correctness on a contract no test
states directly: code reachable from a traced/jitted hot path must never
touch the host (``float()``, ``.item()``, ``np.asarray``,
``jax.device_get``, ``block_until_ready``) — one such call inside the
whole-epoch program either breaks tracing outright or, worse, silently
serializes E*N fused steps behind a device sync.

``@traced`` marks a function as part of that surface.  It is a pure
marker: the decorator returns the function unchanged (so it composes
with ``jax.jit``, ``functools.cached_property`` and friends) and only
sets ``__dl4j_traced__`` for runtime introspection.  The static analyzer
(``analysis/rules.py``) does not import the code at all — it matches the
decorator *name* in the AST — so ``@traced`` works equally on code that
cannot import (fixture snippets, gated backends).

``HOT_PATH_REGISTRY`` is the second prong: function names that are hot
by convention, so pre-annotation code (and code we must not churn) is
covered without edits.  Names are matched bare, module-independent —
every ``_micro_loss`` in the tree is a hot root, which is exactly right
for the one function MultiLayerNetwork and ComputationGraph each supply
to the shared ``nn/train_step.py``.
"""

from __future__ import annotations

from typing import Callable, TypeVar

F = TypeVar("F", bound=Callable)

__all__ = ["traced", "HOT_PATH_REGISTRY"]


def traced(fn: F) -> F:
    """Mark ``fn`` as running under ``jax.jit``/``lax.scan`` tracing (a
    hot root for dl4j-lint's host-sync rule). Identity at runtime."""
    fn.__dl4j_traced__ = True
    return fn


# Functions that are hot roots by NAME, wherever they are defined — the
# shared optimizer step and its grads stages, each network class's
# micro-batch loss, the chunk program factory (its nested ``run`` is hot
# by containment; ``_epoch_run_fn`` is the classes' delegate to it), the
# device_eval kernels, and the traced helpers they lean on. Keep this
# list in sync with docs/static_analysis.md.
#
# profile-readback note: profile collection (monitor/profile
# ``capture_program_profile``, monitor/memory ``sample_hbm_watermark``
# and friends) is a host readback and is only permitted at CHUNK
# BOUNDARIES — between fused dispatches, where drive_epoch_chunks calls
# it. The host-sync rule flags any ``PROFILE_READBACK_CALLS`` name
# (analysis/rules.py) reachable from these roots, exactly like float().
# The same contract covers the run-ledger boundary marks and flight-
# recorder writes (``LEDGER_FLIGHT_CALLS``: ledger_run_start/
# ledger_chunk_start/ledger_chunk_done/ledger_run_end/flight_record) —
# chunk-boundary-only, never inside a traced program.
HOT_PATH_REGISTRY = frozenset({
    # nn/train_step.py — the one optimizer step and the programs that
    # scan it; nn/multilayer.py + nn/graph.py supply _micro_loss
    "optimizer_step",
    "loss_grads",
    "accum_grads",
    "epoch_run_fn",
    "_epoch_run_fn",
    "_micro_loss",
    # perf/epoch_cache.py — runs traced inside the chunk program
    "epoch_schedule",
    # perf/device_eval.py kernels (jitted inside the eval step)
    "confusion_update",
    "regression_update",
    "_flatten_time",
    # monitor/pack.py + resilience/guard.py traced helpers
    "step_metrics",
    "tree_global_norm",
    "tree_all_finite",
    # serving/engine.py — the decode server's jitted program bodies (a
    # host sync here would serialize every online token behind a device
    # readback; the serve loop's ONE sanctioned readback is the
    # per-dispatch token block in serving/server.py, outside these
    # roots). Beside the plain step: the one-step forward it wraps and
    # a round's multi-token-verify body.
    "_serve_prefill_impl",
    "_serve_decode_impl",
    "_serve_decode_loop_impl",
    "_serve_verify_impl",
    "_decode_step_body",
    # serving/fleet/handoff.py — the prefill/decode-split slot movers:
    # pure gather/scatter programs over the pool. The handoff's host
    # readback is once-per-request at the prefill boundary (outside
    # these bodies, in export_slot) — a sync INSIDE them would ride
    # along into every compiled decode-pool program that reuses them.
    "_slot_export_impl",
    "_slot_import_impl",
    # nlp/epoch_kernels.py + nlp/glove.py — the fused embedding programs:
    # in-program pair generation, the masked segment-sum NEG updater, the
    # whole-chunk scan body, and GloVe's fused AdaGrad epoch scan. The
    # chunk DRIVER (drive_skipgram_chunks) is the host boundary — its
    # ledger/heartbeat readbacks must never be reachable from these.
    "skipgram_pair_plan",
    "skipgram_negatives",
    "skipgram_epoch_plan",
    "_neg_epoch_impl",
    "_w2v_chunk_impl",
    "_glove_epoch_impl",
})
