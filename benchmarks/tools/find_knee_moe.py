"""``find_knee.py``'s sweep for a serve cell whose driver brings its own model
builder (``lm_serve_moe``): one sweep, run once on the chip, whose result is
written into the workload file as a plain number.

    python3 benchmarks/tools/find_knee_moe.py --workload olmoe-serve-chat --rates 3,4,5,6,7 --seconds 20 --seeds 0,1

One process, one server, the cell's own length mix offered open-loop for
``--seconds`` at each rate and seed, then a drain. The rule is
``find_knee.py``'s: a rate is **sustained** when the queue is empty or nearly
so when offering ends (``queue_at_end`` <= slots / 4) and at least 95 % of the
requests meet TTFT <= 1 s and TPOT <= 100 ms; the knee is the highest rate
that every seed sustained. The model and the server come from the
``build_model`` and ``build_server`` of the driver the workload file names;
warm-up and request times are ``lm_serve``'s.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def sweep_row(server, traffic, rate, seed, seconds, vocab):
    """Offer one rate for ``seconds`` and drain: the row ``find_knee.py``
    prints, with its verdict."""
    from benchmarks.drivers import lm_serve
    from benchmarks.lib import loadgen

    traffic = json.loads(json.dumps(traffic))
    traffic["arrivals"]["rate_per_s"] = rate
    schedule = loadgen.make_schedule(traffic, seed, seconds, vocab)
    queue_at_end = []

    def on_step(now):
        if now >= seconds and not queue_at_end:
            queue_at_end.append(server.stats()["queue_depth"])

    steps0, slots0 = server.steps, server.slot_dispatches
    res = loadgen.run_open_loop(server, schedule, on_step=on_step)
    times = [lm_serve.request_times(res, o) for o in res.offered]
    ttft = [t[0] for t in times]
    tpot = [t[1] for t in times if t[1] is not None]
    tokens = sum(len(o.request.tokens) for o in res.offered if o.request)
    ok = sum(1 for t in times if t[0] <= 1.0 and (t[1] is None or t[1] <= 0.1))
    steps = server.steps - steps0
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
        "steps": steps,
        "live_slots_per_step": round(
            (server.slot_dispatches - slots0) / max(1, steps), 2),
    }
    row["sustained"] = (row["queue_at_end"] <= server.slots // 4
                        and row["attainment_pct"] >= 95.0)
    server.finished.clear()
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="olmoe-serve-chat")
    ap.add_argument("--rates", default="3,4,5,6,7")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="0,1")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    for k in [k for k in os.environ if k.startswith("DL4J_")]:
        del os.environ[k]
    import jax
    import numpy as np

    from benchmarks import run as harness
    from benchmarks.drivers import lm_serve

    bench = harness._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness._load_json(os.path.join(
        ROOT, "benchmarks", "workloads", args.workload + ".json"))
    cfg_file = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
    config = harness._load_json(os.path.join(ROOT, cfg_file))
    if args.rehearse:
        cell, config = harness._apply_rehearsal(cell, config)
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit("find_knee_moe: needs a TPU")
    driver = importlib.import_module("benchmarks.drivers." + cell["driver"])
    seeds = [int(x) for x in args.seeds.split(",")]
    ctx = types.SimpleNamespace(config=config, cell=cell, seed=seeds[0])
    lm = driver.build_model(ctx)
    server = driver.build_server(ctx, lm)
    t0 = time.monotonic()
    lm_serve.warm_up(server, cell["server"]["buckets"], cell["traffic"],
                     np.random.default_rng([seeds[0], 0x5E7]))
    print(f"warm-up {time.monotonic() - t0:.1f}s; device "
          f"{jax.devices()[0].device_kind}", flush=True)

    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            rows.append(sweep_row(server, cell["traffic"], rate, seed,
                                  args.seconds, lm.vocab_size))
            print(json.dumps(rows[-1]), flush=True)
    held = [rate for rate in {r["rate_per_s"] for r in rows}
            if all(r["sustained"] for r in rows if r["rate_per_s"] == rate)]
    knee = max(held) if held else None
    print(json.dumps({"knee_rate_per_s": knee,
                      "steady_0.8x": round(0.8 * knee, 2) if held else None,
                      "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
