#!/usr/bin/env bash
# Tier-1 verification gate — the exact command from ROADMAP.md.
# Usage: scripts/verify.sh            (full tier-1: everything not 'slow')
#        scripts/verify.sh -m chaos   (extra pytest args narrow the run,
#                                      e.g. just the fault-injection suite)
#        scripts/verify.sh --eval     (just the eval/inference equivalence
#                                      suite: device-vs-host metrics,
#                                      recompile guard, bucketing)
#        scripts/verify.sh --epoch    (just the epoch-pipeline equivalence
#                                      suite: fit_epochs vs per-step
#                                      bitwise, recompile guard, HBM-budget
#                                      fallback)
#        scripts/verify.sh --dp       (just the data-parallel + sharded
#                                      epoch suites on the forced 8-device
#                                      host mesh: SPMD fit_epochs vs
#                                      single-device, parameter averaging
#                                      vs all-reduce, accumulation)
#        scripts/verify.sh --heal     (just the self-healing suite +
#                                      existing chaos cases: NaN-guard
#                                      policies, preemption + bitwise
#                                      elastic resume, save_async,
#                                      checkpoint corruption/eviction)
#        scripts/verify.sh --obs      (just the observability suites —
#                                      metrics pack parity/values,
#                                      registry, tracer, exporters, run
#                                      ledger, flight recorder, fleet
#                                      heartbeats — plus the
#                                      no-bare-counters lint rule and the
#                                      flight-recorder write → kill -9 →
#                                      report round trip)
#        scripts/verify.sh --serve    (just the online-serving suite —
#                                      batched slot decode vs generate
#                                      equivalence, continuous batching,
#                                      compile flatness, prompt ladder,
#                                      loadgen — plus the host-sync lint
#                                      over the serve hot path)
#        scripts/verify.sh --fleet    (just the serve-fleet suite —
#                                      routing policy, failover token
#                                      identity, controller eviction +
#                                      straggler flagging, prefill/
#                                      decode handoff, virtual-clock
#                                      driver, replica-kill chaos — plus
#                                      the host-sync lint over
#                                      serving/fleet/'s traced slot
#                                      movers)
#        scripts/verify.sh --serve-slo (serve overload-control gate —
#                                      deadline sheds at admission/queue/
#                                      in-flight, criticality displacement,
#                                      retry-budget arithmetic + parked
#                                      failovers, hedging races, graceful
#                                      drain token identity, and the
#                                      3x-capacity storm soak's SLO
#                                      asserts — plus the host-sync and
#                                      lock-discipline lint over serving/)
#        scripts/verify.sh --lint     (static analysis gate: the full
#                                      dl4j-lint ruleset over the tree +
#                                      the program-contract checks and
#                                      rule-engine fixtures in
#                                      tests/test_analysis.py; nonzero
#                                      exit on any NEW finding)
#        scripts/verify.sh --profile  (performance observatory: the
#                                      ProgramProfile/HBM-watermark
#                                      suite)
#        scripts/verify.sh --autopilot (always-on fleet: the grant-lease
#                                      protocol, elastic mid-run reshard
#                                      equivalence, goodput-autopilot
#                                      decision suite, and the bounded
#                                      chaos soak (preempt + wedge +
#                                      straggle + evict, 1e-6 final-state
#                                      + goodput-floor asserts) — plus
#                                      the host-sync and lock-discipline
#                                      lint over the resilience modules)
#        scripts/verify.sh --mfu      (mixed-precision MFU push: the
#                                      mixed_bf16 master-weights suite —
#                                      fused-epoch loss parity vs f32,
#                                      flash-vs-xla training parity,
#                                      preempt→resume master round-trip,
#                                      fused updater-sweep depth
#                                      invariance, contracts over the
#                                      mixed program — plus the
#                                      implicit-f32-promotion lint)
#        scripts/verify.sh --nlp      (the fused-embeddings gate: the
#                                      NLP suites + the fused skip-gram
#                                      equivalence/contract tests and the
#                                      sharded DP/row-sharded parity
#                                      suite, plus the host-sync +
#                                      adhoc-out-shardings lint over
#                                      nlp/ (the chunk driver's ledger/
#                                      heartbeat readbacks must never
#                                      ride into the traced programs;
#                                      table placement routes through
#                                      the registry))
#        scripts/verify.sh --mesh     (the sharding-registry gate: the
#                                      DP×TP registry suite — spec
#                                      totality, fused-epoch parity,
#                                      topology reshard, TP serving —
#                                      plus the TP/PP parallel suites,
#                                      and the adhoc-out-shardings
#                                      lint (every placement decision
#                                      routes through the registry))
# The eval/epoch/dp/heal/obs/serve/fleet/serve-slo/lint/profile/mfu/
# mesh tests are part of the default tier-1 run; --eval/--epoch/--dp/
# --heal/--obs/--serve/--fleet/--serve-slo/--lint/--profile/--mfu/
# --mesh are the narrow fast paths for iterating on those surfaces.
set -o pipefail

cd "$(dirname "$0")/.."

TARGET=tests/
if [ "${1:-}" = "--eval" ]; then
    shift
    TARGET=tests/test_eval_device.py
elif [ "${1:-}" = "--epoch" ]; then
    shift
    TARGET=tests/test_epoch_cache.py
elif [ "${1:-}" = "--dp" ]; then
    shift
    TARGET="tests/test_dp_epoch.py tests/test_parallel.py"
elif [ "${1:-}" = "--heal" ]; then
    shift
    TARGET="tests/test_self_healing.py tests/test_resilience.py tests/test_cluster.py"
elif [ "${1:-}" = "--obs" ]; then
    shift
    TARGET="tests/test_telemetry.py tests/test_flight.py"
    # the counters lint rides along with the telemetry suite: no module
    # besides monitor/ may define new bare _*_counter attributes
    # (the old scripts/lint_telemetry.py, absorbed into dl4j-lint)
    python scripts/dl4j_lint.py --select bare-counter || exit 1
    # crash-forensics gate: a flight-recorder child is written to, kill
    # -9'd mid-chunk, and the surviving segments must reconstruct the
    # timeline and classify the death as 'crashed'
    python scripts/flight_report.py --selftest || exit 1
elif [ "${1:-}" = "--serve" ]; then
    shift
    TARGET=tests/test_serving.py
    # the decode loop's host-sync guard rides along: the serve program
    # bodies (serving/engine.py hot roots) must stay free of host
    # readbacks — the one sanctioned [S] token readback lives in
    # server.py, outside the traced surface
    python scripts/dl4j_lint.py --select host-sync-in-hot-path \
        deeplearning4j_tpu/serving || exit 1
elif [ "${1:-}" = "--fleet" ]; then
    shift
    TARGET=tests/test_serving_fleet.py
    # the fleet's traced slot movers (handoff export/import) are hot
    # roots like the engine's program bodies: the per-request handoff
    # readback lives OUTSIDE them (export_slot), and the lint keeps any
    # new sync from riding into the compiled pool programs
    python scripts/dl4j_lint.py --select host-sync-in-hot-path \
        deeplearning4j_tpu/serving || exit 1
elif [ "${1:-}" = "--serve-slo" ]; then
    shift
    TARGET=tests/test_serve_overload.py
    # overload control is control-plane code threaded around the traced
    # decode programs: the shed/hedge/drain paths must add no host syncs
    # to the hot roots and no unlocked cross-thread queue state
    python scripts/dl4j_lint.py \
        --select host-sync-in-hot-path,lock-discipline \
        deeplearning4j_tpu/serving || exit 1
elif [ "${1:-}" = "--lint" ]; then
    shift
    # static-analysis gate: source-level ruleset first (stdlib-only,
    # fails fast), then the jaxpr/HLO program-contract checks + the
    # seeded-violation fixtures that keep the rules themselves honest
    python scripts/dl4j_lint.py || exit 1
    TARGET=tests/test_analysis.py
elif [ "${1:-}" = "--profile" ]; then
    shift
    TARGET=tests/test_profile.py
elif [ "${1:-}" = "--autopilot" ]; then
    shift
    TARGET=tests/test_autopilot.py
    # the always-on layer's control plane is host-side by construction:
    # the lease/autopilot/reshard code must introduce no host syncs into
    # traced programs and no unlocked cross-thread state (the lease's
    # daemon-thread attempt + the autopilot's tick both ride threads)
    python scripts/dl4j_lint.py \
        --select host-sync-in-hot-path,lock-discipline \
        deeplearning4j_tpu/resilience deeplearning4j_tpu/perf || exit 1
elif [ "${1:-}" = "--mfu" ]; then
    shift
    TARGET=tests/test_mixed_precision.py
    # the promotion lint rides along: no matmul operand in a traced hot
    # path may reach a param leaf without policy.cast_compute (the bug
    # class that silently runs the bf16 step at f32 MXU rate)
    python scripts/dl4j_lint.py --select implicit-f32-promotion || exit 1
elif [ "${1:-}" = "--nlp" ]; then
    shift
    TARGET="tests/test_nlp.py tests/test_nlp_fused.py tests/test_distributed_nlp.py"
    # the fused embedding programs are hot roots like the dense chunk
    # programs: no host syncs reachable from the traced pair-gen/updater
    # kernels, and no ad-hoc NamedSharding — syn0/syn1neg placement goes
    # through ShardingRegistry.for_embedding_tables
    python scripts/dl4j_lint.py \
        --select host-sync-in-hot-path,adhoc-out-shardings \
        deeplearning4j_tpu/nlp || exit 1
elif [ "${1:-}" = "--mesh" ]; then
    shift
    TARGET="tests/test_sharding_registry.py tests/test_parallel.py tests/test_dp_epoch.py"
    # the one-mesh discipline rides along: NamedSharding construction /
    # out_shardings= pins belong in parallel/sharding_registry.py (or
    # carry a per-site suppression naming the sanctioned builder)
    python scripts/dl4j_lint.py --select adhoc-out-shardings || exit 1
fi

rm -f /tmp/_t1.log
# force the 8-device host mesh WITHOUT clobbering ambient XLA_FLAGS
# (e.g. --xla_dump_to debugging); conftest.py does the same append for
# direct pytest invocations
case "${XLA_FLAGS:-}" in
    *xla_force_host_platform_device_count*) ;;
    *) XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" ;;
esac
export XLA_FLAGS
# shellcheck disable=SC2086  # TARGET may list several suites
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest $TARGET -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly "$@" \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
exit $rc
