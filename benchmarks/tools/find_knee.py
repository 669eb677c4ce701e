"""Find the highest request rate the serve cell's server sustains: one sweep,
run once on the chip, whose result is written into the workload files as a
plain number. No run of the benchmark ever searches.

    python3 benchmarks/tools/find_knee.py --workload sc2-serve-steady --rates 4,6,8,10,12 --seconds 20 --seeds 0,1

One process, one server: for each rate the same seeded length mix is offered
open-loop for ``--seconds``, then the server drains. A rate is **sustained**
when the backlog does not grow and the users are served: the queue is empty or
nearly so when offering ends (``queue_at_end`` <= slots / 4) and at least 95 %
of the requests meet TTFT <= 1 s and TPOT <= 100 ms (``attainment_pct``). The
drain is no criterion: it lasts as long as the longest answer still being
written. The knee is the highest rate that every seed sustained. The steady
cell runs at 0.8 x the knee, the saturated cell at 1.5 x.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sc2-serve-steady")
    ap.add_argument("--rates", default="8,12,16,20,24,28")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="0",
                    help="each rate runs once per seed: the rows' spread "
                         "across seeds is the cell's noise at that rate")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    for k in [k for k in os.environ if k.startswith("DL4J_")]:
        del os.environ[k]
    import jax
    import numpy as np

    from benchmarks import run as harness
    from benchmarks.drivers import lm_serve
    from benchmarks.lib import loadgen

    bench = harness._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness._load_json(os.path.join(
        ROOT, "benchmarks", "workloads", args.workload + ".json"))
    cfg_file = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
    config = harness._load_json(os.path.join(ROOT, cfg_file))
    if args.rehearse:
        cell, config = harness._apply_rehearsal(cell, config)
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit("find_knee: needs a TPU")
    seeds = [int(x) for x in args.seeds.split(",")]
    ctx = types.SimpleNamespace(config=config, cell=cell, seed=seeds[0])
    lm = lm_serve.build_model(ctx)
    server = lm_serve.build_server(ctx, lm)
    rng = np.random.default_rng([seeds[0], 0x5E7])
    t0 = time.monotonic()
    lm_serve.warm_up(server, cell["server"]["buckets"], cell["traffic"], rng)
    print(f"warm-up {time.monotonic() - t0:.1f}s; device "
          f"{jax.devices()[0].device_kind}", flush=True)

    rows = []
    for rate, seed in [(float(r), sd) for r in args.rates.split(",")
                       for sd in seeds]:
        traffic = json.loads(json.dumps(cell["traffic"]))
        traffic["arrivals"]["rate_per_s"] = rate
        schedule = loadgen.make_schedule(traffic, seed, args.seconds,
                                         lm.vocab_size)
        queue_at_end = []

        def on_step(now):
            if now >= args.seconds and not queue_at_end:
                queue_at_end.append(server.stats()["queue_depth"])

        steps0 = server.steps
        res = loadgen.run_open_loop(server, schedule, on_step=on_step)
        times = [lm_serve.request_times(res, o) for o in res.offered]
        ttft = [t[0] for t in times]
        tpot = [t[1] for t in times if t[1] is not None]
        tokens = sum(len(o.request.tokens) for o in res.offered if o.request)
        ok = sum(1 for t in times
                 if t[0] <= 1.0 and (t[1] is None or t[1] <= 0.1))
        row = {
            "rate_per_s": rate, "seed": seed, "offered": len(res.offered),
            "finished": sum(1 for t in times if t[2]),
            "queue_at_end": queue_at_end[0] if queue_at_end else 0,
            "drain_s": round(res.drain_s, 3),
            "tokens_per_s": round(tokens / res.window_s, 1),
            "ttft_p50_ms": round(1e3 * loadgen.percentile(ttft, 50), 2),
            "ttft_p95_ms": round(1e3 * loadgen.percentile(ttft, 95), 2),
            "tpot_p50_ms": round(1e3 * loadgen.percentile(tpot, 50), 3),
            "tpot_p95_ms": round(1e3 * loadgen.percentile(tpot, 95), 3),
            "attainment_pct": round(100.0 * ok / max(1, len(times)), 2),
            "gen_late_p95_ms": round(1e3 * loadgen.percentile(
                [o.late_s for o in res.offered], 95), 3),
            "steps": server.steps - steps0,
        }
        row["sustained"] = (row["queue_at_end"] <= server.slots // 4
                            and row["attainment_pct"] >= 95.0)
        rows.append(row)
        print(json.dumps(row), flush=True)
        server.finished.clear()
    held = [rate for rate in {r["rate_per_s"] for r in rows}
            if all(r["sustained"] for r in rows if r["rate_per_s"] == rate)]
    print(json.dumps({"knee_rate_per_s": max(held) if held else None,
                      "steady_0.8x": round(0.8 * max(held), 2) if held else None,
                      "saturated_1.5x": round(1.5 * max(held), 2) if held else None,
                      "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
