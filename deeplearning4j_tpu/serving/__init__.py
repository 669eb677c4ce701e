"""Online serving subsystem: continuous batching + batched KV-cache decode.

Every inference surface before this one was batch/offline-oriented (PR
2's shape-bucketed, device-resident eval). This package is the ONLINE
path the north star's "heavy traffic" needs — the TensorFlow-paper
serving/training split applied to the fused-program framework:

- :mod:`~deeplearning4j_tpu.serving.kv_cache` — the slot-based batched
  KV pool: ``[L, S, T_max, Hkv, Dh]`` device-resident K/V with per-slot
  write cursors, so S concurrent requests at different decode positions
  are ONE program's batch dimension.
- :mod:`~deeplearning4j_tpu.serving.engine` — the jitted program set
  (bucket-padded prefill, and ONE decode block a model: the batched
  decode step, or one speculative round drafted from the model's own
  multi-token-prediction module) built on the SAME
  ``TransformerLM._block`` math as training; ``@traced`` hot roots for
  dl4j-lint's host-sync rule.
- :mod:`~deeplearning4j_tpu.serving.scheduler` — request model + bounded
  FIFO admission queue (``DL4J_SERVE_SLOTS``/``DL4J_SERVE_MAX_QUEUE``).
- :mod:`~deeplearning4j_tpu.serving.server` — :class:`DecodeServer`,
  the continuous-batching loop: admit into free slots at step
  boundaries, one batched decode step, retire finished sequences; never
  recompiles past one program per (slot-count, prefill-bucket).
- :mod:`~deeplearning4j_tpu.serving.loadgen` — open-loop Poisson load
  generator + p50/p99/TTFT/TPOT report with per-drop timestamps (the
  ``serve`` bench section).
- :mod:`~deeplearning4j_tpu.serving.fleet` — the multi-replica serve
  fleet (imported explicitly, not re-exported here): replica workers
  under the cluster layer's heartbeat channel, a least-loaded routing
  frontend with failover requeue, the controller's master tick, and
  the ``DL4J_SERVE_ROLE`` prefill/decode split.

See ``docs/inference.md`` §Serving for the architecture and the slot
lifecycle, ``docs/observability.md`` for the serve metric/span taxonomy.
"""

from deeplearning4j_tpu.serving.kv_cache import (  # noqa: F401
    SlotKVCache,
    kv_pool_nbytes,
    max_slots_in_budget,
    resolve_kv_dtype,
)
from deeplearning4j_tpu.serving.engine import DecodeEngine  # noqa: F401
from deeplearning4j_tpu.serving.scheduler import (  # noqa: F401
    CRITICALITIES,
    AdmissionVerdict,
    RequestQueue,
    RetryBudget,
    ServeQueueFull,
    ServeRequest,
    criticality_rank,
    request_cost,
    serve_deadline_s,
    serve_evict_s,
    serve_hedge_s,
    serve_max_queue,
    serve_replicas,
    serve_retry_burst,
    serve_retry_ratio,
    serve_role,
    serve_slots,
)
from deeplearning4j_tpu.serving.server import DecodeServer  # noqa: F401
from deeplearning4j_tpu.serving.loadgen import (  # noqa: F401
    Arrival,
    LoadReport,
    poisson_schedule,
    run_open_loop,
)

__all__ = [
    "AdmissionVerdict", "Arrival", "CRITICALITIES", "DecodeEngine",
    "DecodeServer", "LoadReport", "RequestQueue", "RetryBudget",
    "ServeQueueFull", "ServeRequest", "SlotKVCache",
    "criticality_rank", "kv_pool_nbytes", "max_slots_in_budget",
    "poisson_schedule", "request_cost", "resolve_kv_dtype",
    "run_open_loop", "serve_deadline_s", "serve_evict_s",
    "serve_hedge_s", "serve_max_queue", "serve_replicas",
    "serve_retry_burst", "serve_retry_ratio", "serve_role",
    "serve_slots",
]
