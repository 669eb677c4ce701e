#!/usr/bin/env python
"""Flight-recorder postmortem: reconstruct a dead run's final timeline.

A run that dies mid-work leaves at best one error line and no record of
what the process was doing. With ``DL4J_FLIGHT`` on, the flight
recorder (``deeplearning4j_tpu/monitor/flight.py``) leaves a bounded
segment ring on disk that survives SIGKILL; this script reads whatever
segments survived, prints the final timeline, and classifies the end
state:

- ``clean``     — the last run closed with status ``clean`` (or the
  recorder closed with nothing in flight)
- ``preempted`` — the run stopped at a chunk boundary on a preemption
  latch
- ``wedged``    — the process was ALIVE but stuck: writer heartbeats
  kept arriving long after the last progress record, or explicit wedge
  evidence (grant watchdog, chunk stall) ends the timeline
- ``crashed``   — records stop abruptly (the heartbeats died with the
  progress): SIGKILL, OOM, segfault
- ``reacquired`` — clean-with-recovery: the run finished, but the
  timeline carries ``grant.reacquired`` evidence — a wedged grant was
  rescued by the lease protocol (resilience/lease.py) instead of
  costing the round. Counts as a healthy ending operationally, but is
  reported distinctly so chronic grant flapping stays visible
- ``drained``  — clean-and-planned: ``serve.drain`` evidence shows a
  replica was gracefully retired (streams migrated, zero recompute)
- ``shed-overload`` — clean-but-degraded: ``serve.shed`` evidence
  shows load was dropped (deadline expiry or criticality
  displacement); the serve-overload section splits the sheds by
  where the deadline caught them (queue vs in-flight)

Usage:
    python scripts/flight_report.py <flight-dir>            # human report
    python scripts/flight_report.py --json <flight-dir>     # machine-readable
    python scripts/flight_report.py --recent 40 <flight-dir>
    python scripts/flight_report.py --selftest              # write → kill -9
                                                            # → report round
                                                            # trip (CI)

Exit codes: 0 report produced (any end state), 1 selftest failure,
2 usage/load error. Wired into ``scripts/verify.sh --obs``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deeplearning4j_tpu.monitor.flight import (  # noqa: E402
    classify_end_state,
    load_flight_records,
)


def _fmt_record(rec: dict, t0: float) -> str:
    t = rec.get("t_wall", t0)
    kind = rec.get("kind", "?")
    label = kind
    if kind == "span":
        label = f"span {rec.get('name', '?')}"
        dur = rec.get("duration_s")
        if dur is not None:
            label += f" ({dur:.3f}s)"
    detail = {k: v for k, v in rec.items()
              if k not in ("kind", "name", "t_wall", "t_mono", "_segment",
                           "span_id", "parent_id", "start_s", "end_s",
                           "duration_s", "attrs", "counters")}
    attrs = rec.get("attrs") or {}
    detail.update({k: v for k, v in attrs.items()
                   if isinstance(v, (str, int, float, bool))})
    extra = " ".join(f"{k}={v}" for k, v in sorted(detail.items()))
    return f"  +{t - t0:9.3f}s  {label:<28s} {extra}".rstrip()


def build_report(directory: str, recent: int = 25) -> dict:
    records = load_flight_records(directory)
    verdict = classify_end_state(records)
    runs = [r for r in records if r.get("kind") == "run.start"]
    chunks = sum(1 for r in records if r.get("kind") == "chunk.done")
    by_kind: dict = {}
    for r in records:
        k = r.get("kind", "?")
        if k == "span":
            k = f"span:{r.get('name', '?')}"
        by_kind[k] = by_kind.get(k, 0) + 1
    # serve-overload section: every shed/hedge/drain decision rides the
    # timeline as an event — split the sheds by where they happened
    # (queue-expiry vs in-flight expiry vs displacement) and count the
    # drains, so a storm postmortem reads the WHOLE story from records
    sheds = [r for r in records if r.get("kind") == "serve.shed"]
    serve = {}
    if sheds:
        by_where: dict = {}
        by_reason: dict = {}
        for r in sheds:
            by_where[r.get("where", "?")] = (
                by_where.get(r.get("where", "?"), 0) + 1)
            by_reason[r.get("reason", "?")] = (
                by_reason.get(r.get("reason", "?"), 0) + 1)
        serve["sheds"] = len(sheds)
        serve["sheds_by_where"] = dict(sorted(by_where.items()))
        serve["sheds_by_reason"] = dict(sorted(by_reason.items()))
        serve["expired_in_queue"] = sum(
            1 for r in sheds if r.get("where") == "queue"
            and r.get("reason") == "deadline")
        serve["expired_in_flight"] = sum(
            1 for r in sheds if r.get("where") == "in_flight")
    drains = [r for r in records if r.get("kind") == "serve.drain"]
    if drains:
        serve["drains"] = [
            {"replica": r.get("replica"), "migrated": r.get("migrated"),
             "fallback_failovers": r.get("fallback_failovers")}
            for r in drains]
    hedges = sum(1 for r in records if r.get("kind") == "serve.hedge")
    if hedges:
        serve["hedges"] = hedges
        serve["hedge_wins"] = sum(
            1 for r in records if r.get("kind") == "serve.hedge_win")
    return {
        "directory": directory,
        "end_state": verdict["end_state"],
        "status": verdict.get("status"),
        "evidence": verdict.get("evidence"),
        "n_records": len(records),
        "n_runs_started": len(runs),
        "n_chunks_done": chunks,
        "by_kind": dict(sorted(by_kind.items())),
        "serve_overload": serve or None,
        "timeline": records[-recent:],
    }


def print_report(report: dict, out=None) -> None:
    out = out or sys.stdout
    print(f"flight dir : {report['directory']}", file=out)
    print(f"end state  : {report['end_state'].upper()}"
          + (f" (status={report['status']})" if report.get("status")
             else ""), file=out)
    ev = report.get("evidence") or {}
    if "silent_s" in ev:
        print(f"silence    : {ev['silent_s']}s past last progress "
              f"(heartbeat every {ev.get('heartbeat_interval_s')}s)",
              file=out)
    if ev.get("n_reacquires"):
        print(f"reacquires : {ev['n_reacquires']} wedged grant(s) "
              "rescued by the lease protocol", file=out)
    serve = report.get("serve_overload")
    if serve:
        if serve.get("sheds"):
            print(f"sheds      : {serve['sheds']} "
                  f"(queue-expired {serve.get('expired_in_queue', 0)}, "
                  f"in-flight-expired {serve.get('expired_in_flight', 0)}) "
                  f"by reason {serve.get('sheds_by_reason')}", file=out)
        for d in serve.get("drains", ()):
            print(f"drain      : {d['replica']} migrated={d['migrated']} "
                  f"fallback_failovers={d['fallback_failovers']}",
                  file=out)
        if serve.get("hedges"):
            print(f"hedges     : {serve['hedges']} placed, "
                  f"{serve.get('hedge_wins', 0)} won", file=out)
    print(f"records    : {report['n_records']} surviving "
          f"({report['n_runs_started']} run(s) started, "
          f"{report['n_chunks_done']} chunk(s) completed)", file=out)
    for kind, n in report["by_kind"].items():
        print(f"  {kind:<28s} {n}", file=out)
    timeline = report["timeline"]
    if timeline:
        t0 = timeline[0].get("t_wall", 0.0)
        print(f"final timeline (last {len(timeline)} records):", file=out)
        for rec in timeline:
            print(_fmt_record(rec, t0), file=out)


def selftest() -> int:
    """The write → ``kill -9`` → report round trip the --obs gate runs:
    a child process records a run with a chunk in flight, the parent
    SIGKILLs it mid-run, and the surviving segments must classify as
    ``crashed`` with the run/chunk timeline intact. Stdlib-only — the
    child never imports jax."""
    import signal
    import subprocess
    import tempfile
    import time

    with tempfile.TemporaryDirectory() as d:
        flight_dir = os.path.join(d, "flight")
        child_code = f"""
import sys, time
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
from deeplearning4j_tpu.monitor.flight import FlightRecorder, set_flight
from deeplearning4j_tpu.monitor.ledger import (
    ledger_chunk_done, ledger_chunk_start, ledger_run_start)
rec = FlightRecorder({flight_dir!r}, heartbeat_s_=0.05)
set_flight(rec)
ledger_run_start(model="selftest", epochs=10**6)
i = 0
while True:  # chunks forever, until the parent kills us
    ledger_chunk_start(epoch0=i)
    time.sleep(0.01)
    ledger_chunk_done(epoch0=i)
    i += 1
"""
        proc = subprocess.Popen([sys.executable, "-c", child_code])
        try:
            deadline = time.monotonic() + 30.0
            seen = 0
            while time.monotonic() < deadline:
                seen = sum(1 for r in load_flight_records(flight_dir)
                           if r.get("kind") == "chunk.done")
                if seen >= 3:
                    break
                if proc.poll() is not None:
                    print("flight selftest: child exited early "
                          f"(rc={proc.returncode})", file=sys.stderr)
                    return 1
                time.sleep(0.05)
            if seen < 3:
                print("flight selftest: no chunk records within 30s",
                      file=sys.stderr)
                return 1
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
        report = build_report(flight_dir)
        print_report(report)
        if report["end_state"] != "crashed":
            print(f"flight selftest: expected end state 'crashed', got "
                  f"{report['end_state']!r}", file=sys.stderr)
            return 1
        if report["n_chunks_done"] < 3 or report["n_runs_started"] < 1:
            print("flight selftest: timeline incomplete", file=sys.stderr)
            return 1
        print("flight selftest: ok (kill -9 classified as crashed, "
              f"{report['n_chunks_done']} chunks reconstructed)")
        return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="flight-recorder postmortem report")
    ap.add_argument("directory", nargs="?",
                    help="flight segment directory "
                         "($DL4J_TELEMETRY_DIR/flight)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--recent", type=int, default=25,
                    help="timeline records to include (default 25)")
    ap.add_argument("--selftest", action="store_true",
                    help="write → kill -9 → report round trip (CI)")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()
    if not args.directory:
        ap.error("a flight directory is required (or --selftest)")
    if not os.path.isdir(args.directory):
        print(f"flight_report: {args.directory} is not a directory",
              file=sys.stderr)
        return 2
    report = build_report(args.directory, recent=args.recent)
    if args.json:
        print(json.dumps(report, default=str))
    else:
        print_report(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
