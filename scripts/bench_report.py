#!/usr/bin/env python
"""Bench-trajectory report: round-over-round table + regression gate.

Nothing machine-readable diffs one bench round against the last honest
one unless a tool does it. This reads every ``BENCH_r*.json``
(the driver sidecar shape ``{n, rc, tail, parsed}``; bare result lines
``{metric, value, extras}`` are accepted too, so synthetic fixtures and
fresh ``bench.py`` output both feed it), classifies each round —

- ``ok``     a result line with a non-null headline value and no error
- ``wedge``  an explicit backend-unavailable / wedged-grant error line
- ``error``  no parseable result line, a nonzero rc, or any other error

— prints the trajectory table (headline value, per-section samples/sec,
MFU, guard/telemetry overhead), and with ``--check`` exits nonzero when
the LATEST ok round regresses more than ``--threshold-pct`` against the
best earlier ok round on any tracked series. Each series carries a
DIRECTION: "higher" (throughput-like — a drop regresses) or "lower"
(latency-like, e.g. the serve section's p50/p99 — a rise regresses).
Wedge and error rounds are called out but never scored (a wedge is an
infrastructure fact, not a perf regression) and never used as a
baseline.

Usage:
    python scripts/bench_report.py BENCH_r*.json           # table only
    python scripts/bench_report.py --check BENCH_r*.json   # gate (rc 1
                                                           # on regression)
    python scripts/bench_report.py --check --threshold-pct 10 ...

Exit codes: 0 clean, 1 regression found (``--check``), 2 usage/load
error. Wired into ``scripts/verify.sh --profile``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

WEDGE_MARKERS = ("backend unavailable", "wedge", "did not complete")

# (label, extractor, direction) — direction is "higher" (throughput-like:
# a DROP regresses) or "lower" (latency-like: a RISE regresses); the
# extractor returns None when the round has no honest value for it
TRACKED = [
    ("headline", lambda r: r["value"] if r["status"] == "ok" else None,
     "higher"),
    # the scored MFU series is COST-ANALYSIS-ONLY: rounds whose MFU was
    # derived from the analytic formula (pre-PR-8 artifacts, or a round
    # where cost analysis was unavailable) return None and never enter
    # the trajectory — an analytic number comparing against a compiled
    # one is not the same experiment (the table flags such rounds)
    ("transformer_mfu_pct",
     lambda r: (_dig(r, "transformer_lm", "mfu_pct")
                if transformer_flops_source(r) == "cost_analysis"
                else None), "higher"),
    ("transformer_tokens_per_sec",
     lambda r: _dig(r, "transformer_lm", "tokens_per_sec"), "higher"),
    # mixed-precision step speedup (bf16 step vs the f32-policy step at
    # the same config) — the PR-14 MFU push's direct evidence
    ("train_step_bf16_speedup",
     lambda r: _dig(r, "transformer_lm", "train_step_bf16_speedup"),
     "higher"),
    ("resnet18_mfu_pct",
     lambda r: _dig(r, "resnet18_cifar10", "mfu_pct"), "higher"),
    ("resnet18_samples_per_sec",
     lambda r: _dig(r, "resnet18_cifar10", "samples_per_sec"), "higher"),
    ("mnist_mlp_samples_per_sec",
     lambda r: _dig(r, "mnist_mlp", "samples_per_sec"), "higher"),
    ("lenet5_samples_per_sec",
     lambda r: _dig(r, "lenet5", "samples_per_sec"), "higher"),
    ("gemm_peak_tflops",
     lambda r: _dig(r, "gemm", "peak_achieved_tflops"), "higher"),
    ("epoch_speedup",
     lambda r: _dig(r, "epoch", "speedup"), "higher"),
    ("dp_epoch_samples_per_sec_per_chip",
     lambda r: _dig(r, "dp_epoch", "samples_per_sec_per_chip"), "higher"),
    # the serve section: latency percentiles gate lower-is-better —
    # before per-metric direction existed these could only ride in the
    # table, never fail the gate
    ("serve_tokens_per_sec",
     lambda r: _dig(r, "serve", "tokens_per_sec"), "higher"),
    ("serve_p50_latency_ms",
     lambda r: _dig(r, "serve", "p50_latency_ms"), "lower"),
    ("serve_p99_latency_ms",
     lambda r: _dig(r, "serve", "p99_latency_ms"), "lower"),
    ("serve_ttft_p50_ms",
     lambda r: _dig(r, "serve", "ttft_p50_ms"), "lower"),
    # the serve fleet (PR 13): aggregate throughput and 1->2-replica
    # scaling gate higher; fleet latency percentiles and the
    # failover-recovery time gate lower
    ("serve_fleet_tokens_per_sec",
     lambda r: _dig(r, "serve_fleet", "fleet_tokens_per_sec"), "higher"),
    ("serve_fleet_scaling_2r",
     lambda r: _dig(r, "serve_fleet", "tokens_per_sec_scaling_2r"),
     "higher"),
    ("serve_fleet_p99_latency_ms",
     lambda r: _dig(r, "serve_fleet", "p99_latency_ms_2r"), "lower"),
    ("serve_fleet_ttft_p50_ms",
     lambda r: _dig(r, "serve_fleet", "ttft_p50_ms_2r"), "lower"),
    ("serve_fleet_failover_s",
     lambda r: _dig(r, "serve_fleet", "failover_complete_s"), "lower"),
    # the sharding-registry mesh sweep (PR 17): the most-TP shape's
    # fused step time and per-chip HBM — TP must keep shrinking
    # per-chip residency without breaking whole-epoch fusion
    ("mesh_tp_step_ms",
     lambda r: _dig(r, "mesh_sweep", "tp_step_ms"), "lower"),
    ("mesh_tp_per_chip_hbm_mb",
     lambda r: _dig(r, "mesh_sweep", "tp_per_chip_hbm_mb"), "lower"),
    # the fused embeddings push (PR 18): words/sec gates higher (the
    # section's headline words_per_sec switched from the host loop to
    # the fused program this round), dispatches/epoch must stay at 1
    ("w2v_words_per_sec",
     lambda r: _dig(r, "word2vec", "words_per_sec"), "higher"),
    ("w2v_dispatches_per_epoch",
     lambda r: _dig(r, "word2vec", "dispatches_per_epoch"), "lower"),
]

# direction lookup for scored series; headline:* keys inherit "higher"
DIRECTIONS = {label: direction for label, _, direction in TRACKED}


def series_direction(label: str) -> str:
    if label.startswith("headline:"):
        return "higher"
    return DIRECTIONS.get(label, "higher")

# lower-is-better overhead columns: reported in the table, not gated
OVERHEADS = [
    ("guard_overhead_pct", ("guard", "sentinel_overhead_pct")),
    ("telemetry_overhead_pct", ("telemetry", "pack_overhead_pct")),
    ("flight_overhead_pct", ("flight", "flight_overhead_pct")),
]


def _dig(row: dict, section: str, field: str):
    sec = (row.get("extras") or {}).get(section)
    if not isinstance(sec, dict) or "error" in sec:
        return None
    val = sec.get(field)
    return float(val) if isinstance(val, (int, float)) else None


def transformer_flops_source(row: dict):
    """Where the round's transformer MFU FLOPs came from:
    ``"cost_analysis"`` (the PR-8 dual block with a non-null compiled
    count), ``"analytic"`` (a legacy string block, or a dual block whose
    cost-analysis capture failed), or None (no transformer data)."""
    sec = (row.get("extras") or {}).get("transformer_lm")
    if not isinstance(sec, dict) or "error" in sec:
        return None
    src = sec.get("flops_source")
    if isinstance(src, dict):
        return ("cost_analysis"
                if src.get("cost_analysis_flops") is not None
                else "analytic")
    return "analytic" if src is not None else None


def _dig_ledger(row: dict, field: str = "goodput_pct"):
    """Run-ledger fields from the artifact's telemetry block (PR 9):
    ``extras.telemetry.ledger.{goodput_pct, badput, ...}``. Absent on
    pre-ledger rounds — the column just shows '-'."""
    tel = (row.get("extras") or {}).get("telemetry")
    if not isinstance(tel, dict):
        return None
    ledger = tel.get("ledger")
    if not isinstance(ledger, dict):
        return None
    val = ledger.get(field)
    if field == "badput" and isinstance(val, dict):
        return val
    return float(val) if isinstance(val, (int, float)) else None


def _badput_note(row: dict):
    """Compact 'state=seconds' summary of the ledger's badput."""
    bad = _dig_ledger(row, "badput")
    if not bad:
        return None
    return ",".join(f"{k}={v:.1f}s"
                    for k, v in sorted(bad.items(), key=lambda kv: -kv[1]))


def _round_number(path: str, payload: dict) -> Optional[int]:
    n = payload.get("n")
    if isinstance(n, int):
        return n
    m = re.search(r"r(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else None


def load_round(path: str) -> dict:
    """One BENCH file -> a normalized row. Accepts the driver sidecar
    shape ({n, rc, tail, parsed}) and a bare result line."""
    with open(path) as f:
        payload = json.load(f)
    if "parsed" in payload or "rc" in payload:
        parsed = payload.get("parsed")
        rc = payload.get("rc", 0)
    else:  # a bare bench.py result line
        parsed = payload
        rc = 0
    row = {
        "path": path,
        "round": _round_number(path, payload),
        "rc": rc,
        "metric": None,
        "value": None,
        "unit": None,
        "extras": {},
        "note": "",
    }
    if isinstance(parsed, dict):
        row["metric"] = parsed.get("metric")
        row["value"] = parsed.get("value")
        row["unit"] = parsed.get("unit")
        row["extras"] = parsed.get("extras") or {}
    err = (row["extras"].get("error") or "") if row["extras"] else ""
    if parsed is None:
        row["status"] = "error"
        row["note"] = f"no result line (rc={rc})"
    elif err and any(m in err.lower() for m in WEDGE_MARKERS):
        row["status"] = "wedge"
        row["note"] = err[:90]
    elif err or row["value"] is None or rc != 0:
        row["status"] = "error"
        row["note"] = (err or f"null value (rc={rc})")[:90]
    else:
        row["status"] = "ok"
    return row


def build_series(rows: List[dict]) -> Dict[str, List[Tuple[int, float]]]:
    """{series label: [(round, value), ...]} over ok rounds only, and
    only where the round's headline METRIC matches for the headline
    series (r01's lenet headline and r03's transformer headline are
    different experiments, not a trajectory)."""
    series: Dict[str, List[Tuple[int, float]]] = {}
    for label, extract, _direction in TRACKED:
        pts = []
        for row in rows:
            # unnumbered rounds cannot be ordered into a trajectory
            if row["status"] != "ok" or row["round"] is None:
                continue
            val = extract(row)
            if val is not None:
                key = label
                if label == "headline":
                    key = f"headline:{row['metric']}"
                pts.append((key, row["round"], val))
        for key, rnd, val in pts:
            series.setdefault(key, []).append((rnd, val))
    return series


def find_regressions(series: Dict[str, List[Tuple[int, float]]],
                     threshold_pct: float) -> List[str]:
    """Latest ok point vs the best EARLIER ok point per series, where
    "best" follows the series direction: max for higher-is-better
    (throughput — a drop regresses), min for lower-is-better (latency —
    a rise regresses)."""
    out = []
    for label, pts in sorted(series.items()):
        pts = sorted(pts)
        if len(pts) < 2:
            continue
        (last_round, last), earlier = pts[-1], pts[:-1]
        if series_direction(label) == "lower":
            best_round, best = min(earlier, key=lambda p: p[1])
            if best <= 0:
                continue
            delta_pct = 100.0 * (last - best) / best
            verb = "above"
        else:
            best_round, best = max(earlier, key=lambda p: p[1])
            if best <= 0:
                continue
            delta_pct = 100.0 * (best - last) / best
            verb = "below"
        if delta_pct > threshold_pct:
            out.append(
                f"{label}: r{last_round:02d} = {last:,.1f} is "
                f"{delta_pct:.1f}% {verb} r{best_round:02d} = {best:,.1f} "
                f"(threshold {threshold_pct:.0f}%)")
    return out


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float) and abs(v) >= 1000:
        return f"{v:,.0f}"
    return f"{v:g}"


def print_table(rows: List[dict], out=None) -> None:
    out = out or sys.stdout
    cols = ["round", "status", "headline", "value", "tf_mfu%",
            "rn_mfu%", "guard_ov%", "telem_ov%", "goodput%", "badput",
            "note"]
    table = []
    for row in rows:
        note = row["note"]
        if (row["status"] == "ok"
                and transformer_flops_source(row) == "analytic"):
            # the MFU printed beside it came from the hand formula, not
            # the compiled program — excluded from the scored series
            flag = "[flops_source!=cost_analysis]"
            note = f"{note} {flag}".strip() if note else flag
        table.append([
            f"r{row['round']:02d}" if row["round"] is not None else "?",
            row["status"].upper() if row["status"] != "ok" else "ok",
            (row["metric"] or "-")[:44],
            _fmt(row["value"]),
            _fmt(_dig(row, "transformer_lm", "mfu_pct")),
            _fmt(_dig(row, "resnet18_cifar10", "mfu_pct")),
            _fmt(_dig(row, *OVERHEADS[0][1])),
            _fmt(_dig(row, *OVERHEADS[1][1])),
            _fmt(_dig_ledger(row)),
            _badput_note(row) or "-",
            note,
        ])
    widths = [max(len(str(r[i])) for r in [cols] + table)
              for i in range(len(cols))]
    for r in [cols] + table:
        print("  ".join(str(c).ljust(w) for c, w in zip(r, widths)),
              file=out)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="bench trajectory table + regression gate")
    ap.add_argument("files", nargs="+", help="BENCH_r*.json artifacts")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when a tracked series regresses")
    ap.add_argument("--threshold-pct", type=float, default=20.0,
                    help="regression threshold (default 20%%)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output (compact per-round "
                         "rows + series + regressions) instead of the "
                         "table")
    args = ap.parse_args(argv)

    rows = []
    for path in sorted(args.files):
        try:
            rows.append(load_round(path))
        except (OSError, json.JSONDecodeError) as e:
            print(f"bench_report: cannot load {path}: {e}",
                  file=sys.stderr)
            return 2
    rows.sort(key=lambda r: (r["round"] is None, r["round"]))

    series = build_series(rows)
    regressions = find_regressions(series, args.threshold_pct)

    if args.json:
        # compact rows (extras are megabytes in real artifacts — keep
        # the machine-readable shape to the scored/reported fields)
        compact = []
        for row in rows:
            entry = {
                "round": row["round"], "status": row["status"],
                "metric": row["metric"], "value": row["value"],
                "unit": row["unit"], "rc": row["rc"],
                "note": row["note"],
                "goodput_pct": _dig_ledger(row),
                "badput": _dig_ledger(row, "badput"),
                "transformer_flops_source": transformer_flops_source(row),
            }
            for label, extract, _direction in TRACKED[1:]:
                entry[label] = extract(row)
            for label, keys in OVERHEADS:
                entry[label] = _dig(row, *keys)
            compact.append(entry)
        print(json.dumps({
            "rounds": compact,
            "series": {k: v for k, v in sorted(series.items())},
            "directions": {k: series_direction(k) for k in series},
            "threshold_pct": args.threshold_pct,
            "regressions": regressions,
        }))
        return 1 if (regressions and args.check) else 0

    print_table(rows)
    bad = [r for r in rows if r["status"] != "ok"]
    if bad:
        print()
        for row in bad:
            rid = (f"r{row['round']:02d}" if row["round"] is not None
                   else "r??")
            print(f"  !! {rid} is a "
                  f"{row['status'].upper()} round — excluded from "
                  f"regression scoring: {row['note']}")

    if regressions:
        print("\nREGRESSIONS:")
        for r in regressions:
            print(f"  {r}")
        if args.check:
            return 1
    elif args.check:
        print("\nno regressions beyond "
              f"{args.threshold_pct:.0f}% across "
              f"{sum(1 for r in rows if r['status'] == 'ok')} ok "
              f"round(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
