"""Serve fleet: multi-replica routing, failover, prefill/decode split.

PRs 10-11 built the single-replica online engine (slot-batched KV pool,
continuous batching, speculative rounds of a model's own module); this
package scales it OUT — ROADMAP item 3's fleet phase, the TensorFlow-
paper serving/training split (arXiv 1605.08695) taken to fleet scale on
the cluster primitives that already exist (``parallel/statetracker``,
PR-9 heartbeat metric payloads, the master-tick eviction pattern):

- :mod:`~deeplearning4j_tpu.serving.fleet.replica` —
  :class:`ServeReplica`: a ``DecodeServer`` in a worker loop that
  registers with the ``StateTracker`` and posts per-beat serve payloads
  ``{occupancy, queue_depth, free_slots, ttft_p50, tpot_s,
  tokens_per_sec}``.
- :mod:`~deeplearning4j_tpu.serving.fleet.router` —
  :class:`FleetRouter`: least-loaded admission (free-slots-first,
  TTFT-aware tiebreak), bounded per-replica queues with overflow spill,
  sticky affinity, and failover requeue with the prompt re-prefilled
  (greedy streams keep their emitted prefix; completed output is
  token-identical to an unfailed run).
- :mod:`~deeplearning4j_tpu.serving.fleet.controller` —
  :class:`FleetController`: the master tick — aggregate fleet gauges,
  flag TPOT stragglers (shared outlier rule with the training master),
  evict silent/crashed replicas with evidence-logged decisions, requeue
  their in-flight requests onto survivors.
- :mod:`~deeplearning4j_tpu.serving.fleet.handoff` — the
  prefill/decode split (``DL4J_SERVE_ROLE``): prefill replicas export
  ``(kv_slab, cursor, rng_key)`` packages a decode replica installs
  into a free slot (``_slot_export_impl``/``_slot_import_impl`` are
  ``@traced`` hot roots).
- :mod:`~deeplearning4j_tpu.serving.fleet.driver` —
  :class:`FleetLoadDriver`: the bench's per-replica virtual-clock
  replay (real measured dispatch costs, chip-per-replica timelines).

See ``docs/inference.md`` §Serve fleet for the architecture, routing
policy, and failover contract; ``docs/observability.md`` for the
fleet-serve metric/span catalog.
"""

from deeplearning4j_tpu.serving.fleet.controller import (  # noqa: F401
    FleetController,
)
from deeplearning4j_tpu.serving.fleet.driver import (  # noqa: F401
    FleetLoadDriver,
)
from deeplearning4j_tpu.serving.fleet.handoff import (  # noqa: F401
    SlotHandoff,
    export_slot,
    install_slot,
    make_install,
)
from deeplearning4j_tpu.serving.fleet.replica import (  # noqa: F401
    ServeReplica,
)
from deeplearning4j_tpu.serving.fleet.router import (  # noqa: F401
    FleetRequest,
    FleetRouter,
    FleetSaturated,
)

__all__ = [
    "FleetController", "FleetLoadDriver", "FleetRequest", "FleetRouter",
    "FleetSaturated", "ServeReplica", "SlotHandoff", "export_slot",
    "install_slot", "make_install",
]
