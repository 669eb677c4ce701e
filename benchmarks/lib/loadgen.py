"""Seeded request traffic and an open loop that times from the due instant.

One generator reads a traffic mix's parameters (a ``traffic`` object in a
workload file):

    {"arrivals": {"rate_per_s": 12.0},
     "prompt_tokens": {"median": 1024, "sigma": 1.0, "min": 64, "max": 16128},
     "output_tokens": {"median": 64, "sigma": 0.7, "min": 8, "max": 256},
     "max_total_tokens": 16384}

The amount of work is fixed by the mix and the window, and only its order and
timing are drawn from the seed, so that runs with different seeds compare:

- the number of arrivals is ``round(rate * seconds)``; their instants are
  sorted uniform draws over the window, which is a Poisson process given its
  count (a cell with other arrivals brings a generator file of its own);
- lengths are the log-normal's quantiles at ``(i + 0.5) / n``, clipped, dealt
  to the arrivals in a seeded order: every seed offers the same multiset of
  prompt and output lengths.

The same seed gives the same schedule.

``"schedule_seed": <n>`` in the mix pins the trace: arrival instants, lengths
and their pairing then come from that number, and the run's seed draws only
the token ids and each request's sampling seed. A tail such as a p95 over a few
hundred requests swings by tens of per cent between Poisson traces of one mix
(PERF.md section 2); a cell that bounds a tail replays one trace, a cell that
bounds a throughput can leave the trace to the run's seed.

The loop is open: a request is offered when its due instant has passed,
whatever the server is doing. Its latency counts from the **due** instant,
not from the call to ``submit``: a single-threaded server that is inside a
long prefill cannot accept the next request, and that wait is the user's.
How late each submit ran is kept and reported, so that a starved generator
is not read as a fast server.
"""

from __future__ import annotations

import statistics
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np


@dataclass
class Arrival:
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int
    seed: int


def _lognormal_ints(rng, n, spec) -> np.ndarray:
    """The distribution's ``n`` evenly spaced quantiles, in a seeded order."""
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return x[rng.permutation(n)]


def make_schedule(traffic: dict, seed: int, seconds: float,
                  vocab_size: int) -> List[Arrival]:
    """``round(rate * seconds)`` arrivals, all due before ``seconds``."""
    rng = np.random.default_rng([seed, 0x10AD])          # tokens, request seeds
    trace_rng = np.random.default_rng(                   # instants, lengths
        [int(traffic.get("schedule_seed", seed)), 0x7ACE])
    n = max(1, int(round(float(traffic["arrivals"]["rate_per_s"]) * seconds)))
    due = np.sort(trace_rng.uniform(0.0, seconds, n))
    plens = _lognormal_ints(trace_rng, n, traffic["prompt_tokens"])
    outs = _lognormal_ints(trace_rng, n, traffic["output_tokens"])
    cap = int(traffic["max_total_tokens"])
    plens = np.minimum(plens, cap - outs)
    seeds = rng.integers(0, 2**31 - 1, n)
    return [Arrival(float(due[i]),
                    rng.integers(1, vocab_size, int(plens[i]), np.int32),
                    int(outs[i]), int(seeds[i])) for i in range(n)]


@dataclass
class Offered:
    arrival: Arrival
    late_s: float                  # submit instant - due instant
    request: Optional[object]      # the server's request object, if admitted
    error: Optional[str] = None    # refusal or exception text


@dataclass
class LoopResult:
    t0: float                      # the clock at which due_s == 0
    window_s: float                # offering started -> loop ended
    offered: List[Offered] = field(default_factory=list)
    drain_s: float = 0.0           # last arrival due -> loop ended (if later)


def run_open_loop(server, schedule: List[Arrival], *,
                  clock: Callable[[], float] = time.monotonic,
                  sleep: Callable[[float], None] = time.sleep,
                  cut_s: Optional[float] = None,
                  on_step: Optional[Callable[[float], None]] = None,
                  step_span=None) -> LoopResult:
    """Offer ``schedule`` to ``server`` (``try_submit`` / ``step`` /
    ``busy``) from one thread. Without ``cut_s`` the loop ends when
    every arrival was offered and the server is empty; with it, at
    ``cut_s`` seconds whatever is still in flight."""
    res = LoopResult(t0=clock(), window_s=0.0)
    i, n = 0, len(schedule)
    while True:
        now = clock() - res.t0
        if cut_s is not None and now >= cut_s:
            break
        while i < n and schedule[i].due_s <= now:
            a = schedule[i]
            i += 1
            late = (clock() - res.t0) - a.due_s
            try:
                verdict = server.try_submit(a.prompt, a.max_new_tokens,
                                            seed=a.seed)
            except Exception as e:      # recorded as a failed request
                res.offered.append(Offered(a, late, None, repr(e)[:200]))
                continue
            if verdict.admitted:
                res.offered.append(Offered(a, late, verdict.request))
            else:
                res.offered.append(Offered(a, late, None,
                                           verdict.reason or "refused"))
        if i >= n and cut_s is None and not server.busy():
            break
        if step_span is not None:
            with step_span():
                progressed = server.step()
        else:
            progressed = server.step()
        if on_step is not None:
            on_step(clock() - res.t0)
        if not progressed:
            nxt = schedule[i].due_s if i < n else (cut_s or 0.0)
            gap = nxt - (clock() - res.t0)
            if gap > 0:
                sleep(min(gap, 0.002))
    res.window_s = clock() - res.t0
    if schedule:
        res.drain_s = max(0.0, res.window_s - schedule[-1].due_s)
    return res


def percentile(xs, q: float) -> float:
    """Percentile that treats missing outcomes as +inf: ``xs`` may hold
    ``float('inf')``; linear interpolation is between finite neighbours
    only, so one inf past the rank makes the result inf."""
    xs = sorted(xs)
    if not xs:
        return float("nan")
    rank = (len(xs) - 1) * q / 100.0
    lo = int(np.floor(rank))
    hi = int(np.ceil(rank))
    if xs[hi] == float("inf"):
        return float("inf")
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))
