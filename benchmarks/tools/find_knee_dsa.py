"""``find_knee_moe.py``'s sweep with the cell's own limits
(``glm-serve-longdoc``): time to first token grows with the prompt, so a
request is within the limits when TTFT <= ``limits.ttft_s`` +
``limits.ttft_s_per_1k_prompt`` x its prompt's length / 1,024 and TPOT <=
``limits.tpot_s`` (``traffic.limits`` in the workload file).

    python3 benchmarks/tools/find_knee_dsa.py --workload glm-serve-longdoc --rates 0.4,0.3,0.2 --seconds 51 --seeds 0

(``find_knee_moe.py``'s arguments: name the workload.)

The rule is ``find_knee_moe.py``'s otherwise: a rate is **sustained** when the
queue is empty or nearly so when offering ends (``queue_at_end`` <= slots / 4)
and at least 95 per cent of the requests are within the limits; the knee is
the highest rate that every seed sustained. At a fraction of a request a
second ``--seconds`` of offering is a dozen requests, of which 95 per cent
allows none to miss: a rate is offered for as long as it takes to offer
``MIN_REQUESTS``, if that is longer. ``in_flight_at_end`` counts what held a
slot when offering ended: this server takes a request out of the queue as soon
as a slot is free and prefills it a block a step, so a backlog shows there and
not in the queue. Give the rates from the highest down: the sweep ends at the
first one that is sustained, which is the knee, and the rates after it get a
row that says ``skipped`` (two to five minutes of chip each, the longest for
the lowest rates); the rows above the knee are the saturation readings. Each
row also lists every request's prompt length, TTFT and
TPOT, so that other limits can be judged from one sweep. One process, one
server; the model and the server come from the driver the workload file names.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MIN_REQUESTS = 40       # of which 95 per cent allows two to miss
_sustained = []         # the first rate that held, in this process


def sweep_row(server, traffic, rate, seed, seconds, vocab):
    """Offer one rate for ``seconds`` and drain: ``find_knee_moe``'s row,
    its attainment counted against ``traffic["limits"]``."""
    from benchmarks.drivers import lm_serve
    from benchmarks.lib import loadgen

    if _sustained:
        return {"rate_per_s": rate, "seed": seed, "sustained": False,
                "skipped": f"{_sustained[0]} requests/s was sustained"}
    lim = traffic["limits"]
    traffic = json.loads(json.dumps(traffic))
    traffic["arrivals"]["rate_per_s"] = rate
    seconds = max(seconds, MIN_REQUESTS / rate)
    schedule = loadgen.make_schedule(traffic, seed, seconds, vocab)
    queue_at_end = []

    def on_step(now):
        if now >= seconds and not queue_at_end:
            queue_at_end.append((server.stats()["queue_depth"],
                                 server.slots - server.free_slot_count()))

    steps0, slots0 = server.steps, server.slot_dispatches
    res = loadgen.run_open_loop(server, schedule, on_step=on_step)
    times = [lm_serve.request_times(res, o) for o in res.offered]
    ttft = [t[0] for t in times]
    tpot = [t[1] for t in times if t[1] is not None]
    tokens = sum(len(o.request.tokens) for o in res.offered if o.request)
    ok = sum(1 for o, t in zip(res.offered, times)
             if t[0] <= lim["ttft_s"] + lim["ttft_s_per_1k_prompt"]
             * len(o.arrival.prompt) / 1024
             and (t[1] is None or t[1] <= lim["tpot_s"]))
    steps = server.steps - steps0
    row = {
        "rate_per_s": rate, "seed": seed, "offered": len(res.offered),
        "offered_s": round(seconds, 1),
        "finished": sum(1 for t in times if t[2]),
        "queue_at_end": queue_at_end[0][0] if queue_at_end else 0,
        "in_flight_at_end": queue_at_end[0][1] if queue_at_end else 0,
        "drain_s": round(res.drain_s, 3),
        "tokens_per_s": round(tokens / res.window_s, 1),
        "ttft_p50_ms": round(1e3 * loadgen.percentile(ttft, 50), 2),
        "ttft_p95_ms": round(1e3 * loadgen.percentile(ttft, 95), 2),
        "tpot_p50_ms": round(1e3 * loadgen.percentile(tpot, 50), 3),
        "tpot_p95_ms": round(1e3 * loadgen.percentile(tpot, 95), 3),
        "attainment_pct": round(100.0 * ok / max(1, len(times)), 2),
        "steps": steps,
        "live_slots_per_step": round(
            (server.slot_dispatches - slots0) / max(1, steps), 2),
    }
    # each request's own numbers, so that other limits can be judged later
    row["requests"] = [[len(o.arrival.prompt), round(1e3 * t[0], 1),
                        None if t[1] is None else round(1e3 * t[1], 2)]
                       for o, t in zip(res.offered, times)]
    row["sustained"] = (row["queue_at_end"] <= server.slots // 4
                        and row["attainment_pct"] >= 95.0)
    if row["sustained"]:
        _sustained.append(rate)
    server.finished.clear()
    return row


def main():
    """``find_knee_moe.main`` (arguments, model, server, warm-up, the knee's
    rule) with this file's ``sweep_row``."""
    from benchmarks.tools import find_knee_moe

    find_knee_moe.sweep_row = sweep_row
    find_knee_moe.main()


if __name__ == "__main__":
    main()
