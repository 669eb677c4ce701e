"""How far the median TPOT of a block-prefill serving cell moves when only
the timing moves: a discrete-event model of ``DecodeServer._admit_blocks``
under ``loadgen.run_open_loop`` (every arrived request takes a slot; a
scheduler step runs ONE prefill block, of the prompt that has waited longest
for one, then one decode step for the slots that owe a token), run over the
cell's pinned trace many times with the step times drawn a little apart, as
weights from another ``--seed`` draw them. Nothing here is a chip number: the
model has three constants fitted to chip readings, and says which pinned
traces put a request's decode on the edge of another's prefill block.

    python3 benchmarks/tools/trace_steadiness.py --workload glm53-serve-agent --schedule-seeds 0,34

Why it exists (PERF.md section 6, PR 51): ``serve_tpot_p50_ms`` of 16
requests is the mean of the 8th and 9th of values that lie 9 to 70 ms apart,
and a request of a few hundred tokens that decodes under one block more or
less (0.2-0.3 s) moves by milliseconds. On the trace of ``schedule_seed`` 0
six seeds read 11.26-11.46 and 11.93-11.96 ms (5.4 % by the driver's rule);
the model reads 3-8 % there and about 1 % on the trace the cell now pins.

A block of a prompt in bucket R takes ``a + b R / 57344`` seconds and a
decode step ``td``; ``FITS`` are the (a, b, td) that reproduce the chip's
readings on the first trace best (steps, window, TTFT p50 / p95, TPOT p50 /
p95; my chip runs, PR 51, call 3). A draw moves a and b by up to 3 % and td by
up to 1 %, and every step by 0.3 % more.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import loadgen  # noqa: E402

BLOCK = 2048
FITS = [(0.10, 0.20, 0.00946), (0.09, 0.22, 0.00946), (0.11, 0.18, 0.0092),
        (0.12, 0.16, 0.00946), (0.14, 0.10, 0.00946)]
HOST_S = 0.0003         # a scheduler step's host time


def simulate(schedule, buckets, a, b, td, rng):
    """``schedule``: (due_s, prompt tokens, new tokens) a request ->
    (TTFT, TPOT) a request, in seconds."""
    top = max(buckets)
    t, i, n = 0.0, 0, len(schedule)
    prefilling = collections.OrderedDict()  # request -> [blocks, done, s]
    owed, first, finish = {}, {}, {}

    def jitter():
        return 1.0 + rng.normal(0, 0.003)

    while True:
        while i < n and schedule[i][0] <= t:
            prompt = schedule[i][1]
            rung = next(x for x in buckets if x >= prompt)
            prefilling[i] = [-(-prompt // BLOCK), 0, a + b * rung / top]
            i += 1
        if i >= n and not prefilling and not owed:
            break
        if not prefilling and not owed:
            t = schedule[i][0]
            continue
        if prefilling:
            k, state = next(iter(prefilling.items()))
            t += state[2] * jitter()
            state[1] += 1
            if state[1] < state[0]:
                prefilling.move_to_end(k)
            else:
                del prefilling[k]
                first[k] = t
                owed[k] = schedule[k][2] - 1
        if owed:
            t += td * jitter()
            for k in list(owed):
                owed[k] -= 1
                if owed[k] <= 0:
                    del owed[k]
                    finish[k] = t
        t += HOST_S
    return [(first[k] - due, (finish[k] - first[k]) / max(1, new - 1))
            for k, (due, _, new) in enumerate(schedule)]


def spread(values):
    """The driver's: the quartiles' distance over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--schedule-seeds", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--draws", type=int, default=60)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           args.workload + ".json")) as f:
        cell = json.load(f)
    traffic, buckets = cell["traffic"], cell["server"]["buckets"]
    seeds = ([int(s) for s in args.schedule_seeds.split(",")]
             if args.schedule_seeds else [int(traffic["schedule_seed"])])
    for seed in seeds:
        schedule = [(x.due_s, len(x.prompt), x.max_new_tokens)
                    for x in loadgen.make_schedule(
                        {**traffic, "schedule_seed": seed}, 0, args.seconds,
                        2)]
        row = {"schedule_seed": seed, "requests": len(schedule), "fits": []}
        for a, b, td in FITS:
            rng = np.random.default_rng(seed)
            p50 = []
            for _ in range(args.draws):
                out = simulate(schedule, buckets,
                               a * rng.uniform(0.97, 1.03),
                               b * rng.uniform(0.97, 1.03),
                               td * rng.uniform(0.99, 1.01), rng)
                p50.append(1e3 * loadgen.percentile([x[1] for x in out], 50))
            row["fits"].append({
                "a": a, "b": b, "td": td,
                "tpot_p50_ms": round(statistics.median(p50), 3),
                "spread": round(spread(p50), 4),
                "range": round((max(p50) - min(p50))
                               / statistics.median(p50), 4)})
        row["worst_spread"] = max(f["spread"] for f in row["fits"])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
