"""Resilience layer: deterministic fault injection, unified retry/backoff,
hung-step watchdog.

The control plane (``parallel/cluster.py``, ``parallel/statetracker.py``,
``parallel/registry.py``, ``datasets/fetchers.py``) programs against this
package instead of hand-rolling sleeps and bare ``except`` clauses:

- :mod:`~deeplearning4j_tpu.resilience.faults` — named injection sites
  activated per-test (``inject``) or per-process (``DL4J_FAULTS=``), with
  deterministic schedules; zero overhead when inactive.
- :mod:`~deeplearning4j_tpu.resilience.retry` — one ``RetryPolicy``
  (exponential backoff + full jitter, deadline, retryable filter,
  injectable sleep) replacing every ad-hoc retry loop.
- :mod:`~deeplearning4j_tpu.resilience.watchdog` — ``StepWatchdog`` flags
  hung training steps past a deadline (the slow/hung-host detector SPMD
  needs, since a blocked collective never crashes).
- :mod:`~deeplearning4j_tpu.resilience.guard` — the ``DL4J_NAN_GUARD``
  divergence policy behind the fused pipeline's in-program numeric
  sentinel (``skip``/``halve_lr``/``raise``/``off``) and
  :class:`TrainingDivergedError`.
- :mod:`~deeplearning4j_tpu.resilience.preemption` — ``PreemptionGuard``
  latches SIGTERM / injected ``preempt.chunk`` faults so fused training
  checkpoints and stops at a chunk boundary instead of dying mid-run.
- :mod:`~deeplearning4j_tpu.resilience.lease` — ``GrantLease`` bounded
  watchdog around a backend acquisition that may block (serve replica
  warm-up): a wedged attempt releases and re-acquires under escalating
  backoff instead of recording an error line and dying.
- :mod:`~deeplearning4j_tpu.resilience.autopilot` —
  ``GoodputAutopilot`` closes the observe→act loop over the PR-9 fleet
  gauges: goodput below floor / straggler flagged / heartbeat silence
  become evict/reshard/re-admit decisions, each evidence-logged as an
  ``autopilot.decision`` event.

Checkpoint integrity verification lives with its writer
(``parallel.cluster.FaultTolerantTrainer``): sha256 manifest sidecars on
save, verify + fall back to the next-older checkpoint on resume. See
``docs/resilience.md`` for the failure model.
"""

from deeplearning4j_tpu.resilience.faults import (  # noqa: F401
    FaultInjected,
    FaultPoint,
    clear,
    delay,
    fail_nth,
    fail_rate,
    fail_times,
    fault_point,
    inject,
    install,
    install_from_env,
    parse_spec,
    uninstall,
)
from deeplearning4j_tpu.resilience.autopilot import (  # noqa: F401
    AutopilotDecision,
    GoodputAutopilot,
    autopilot_enabled,
    goodput_floor,
)
from deeplearning4j_tpu.resilience.guard import (  # noqa: F401
    TrainingDivergedError,
    nan_guard_policy,
    tree_all_finite,
)
from deeplearning4j_tpu.resilience.lease import (  # noqa: F401
    GrantLease,
    GrantWedgedError,
    grant_lease_s,
    grant_reacquires,
)
from deeplearning4j_tpu.resilience.preemption import (  # noqa: F401
    PreemptionGuard,
)
from deeplearning4j_tpu.resilience.retry import (  # noqa: F401
    RetryError,
    RetryPolicy,
    no_jitter,
)
from deeplearning4j_tpu.resilience.watchdog import StepWatchdog  # noqa: F401

# chaos runs of real entry points: DL4J_FAULTS takes effect on first import
install_from_env()
