"""``lib/loadgen.py``: the schedule is a function of the seed, and latencies
count from the due instant."""

import types

import numpy as np

from benchmarks.lib import loadgen

TRAFFIC = {
    "arrivals": {"rate_per_s": 50.0},
    "prompt_tokens": {"median": 64, "sigma": 1.0, "min": 8, "max": 192},
    "output_tokens": {"median": 16, "sigma": 0.7, "min": 2, "max": 64},
    "max_total_tokens": 256,
}


def test_same_seed_same_schedule_other_seed_differs():
    a = loadgen.make_schedule(TRAFFIC, 7, 10.0, 1000)
    b = loadgen.make_schedule(TRAFFIC, 7, 10.0, 1000)
    c = loadgen.make_schedule(TRAFFIC, 8, 10.0, 1000)
    assert len(a) == len(b) == len(c) == 500
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert x.seed == y.seed and np.array_equal(x.prompt, y.prompt)
    assert [x.due_s for x in a] != [x.due_s for x in c]
    # ... but every seed offers the same work: the same multiset of lengths
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert sorted(x.max_new_tokens for x in a) == sorted(
        x.max_new_tokens for x in c)


def test_schedule_seed_pins_the_trace_and_leaves_tokens_to_the_seed():
    pinned = dict(TRAFFIC, schedule_seed=3)
    a = loadgen.make_schedule(pinned, 7, 10.0, 1000)
    b = loadgen.make_schedule(pinned, 8, 10.0, 1000)
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == [
        (x.due_s, len(x.prompt), x.max_new_tokens) for x in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.seed for x in a] != [x.seed for x in b]


def test_schedule_respects_the_mix():
    s = loadgen.make_schedule(TRAFFIC, 1, 60.0, 1000)
    assert all(0 < x.due_s < 60.0 for x in s)
    assert len(s) == 3000
    lens = sorted(len(x.prompt) for x in s)
    assert lens[len(lens) // 2] in (63, 64, 65)         # the median asked for
    assert all(8 <= len(x.prompt) <= 192 for x in s)
    assert all(2 <= x.max_new_tokens <= 64 for x in s)
    assert all(len(x.prompt) + x.max_new_tokens <= 256 for x in s)
    assert all(x.prompt.min() >= 1 and x.prompt.max() < 1000 for x in s)
    gaps = np.diff([x.due_s for x in s])
    assert 0.8 < gaps.std() / gaps.mean() < 1.2          # Poisson: cv 1


class FakeServer:
    """Admits everything; each step takes 1 s of fake time and finishes the
    oldest queued request."""

    def __init__(self, clock):
        self.clock = clock
        self.q = []

    def try_submit(self, prompt, max_new_tokens, seed=0):
        req = types.SimpleNamespace(submit_s=self.clock.now, done_s=None)
        self.q.append(req)
        return types.SimpleNamespace(admitted=True, request=req, reason=None)

    def busy(self):
        return bool(self.q)

    def step(self):
        if not self.q:
            return False
        self.clock.now += 1.0
        self.q.pop(0).done_s = self.clock.now
        return True


class FakeClock:
    now = 100.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


def test_open_loop_times_from_due_and_reports_lateness():
    clock = FakeClock()
    server = FakeServer(clock)
    prompt = np.ones(4, np.int32)
    # three requests due together: the one-thread server takes 1 s each
    sched = [loadgen.Arrival(0.0, prompt, 2, 0),
             loadgen.Arrival(0.1, prompt, 2, 1),
             loadgen.Arrival(0.2, prompt, 2, 2)]
    res = loadgen.run_open_loop(server, sched, clock=clock, sleep=clock.sleep)
    assert len(res.offered) == 3
    # the first is on time; the other two came due while the server was
    # inside its first step and are submitted late by what was left of it
    lates = [round(o.late_s, 6) for o in res.offered]
    assert lates == [0.0, 0.9, 0.8]
    # latency from the DUE instant: 1.0, 1.9, 2.8 -- from submit the last two
    # would read 1.0 and 2.0 and hide the wait the first step imposed
    from_due = [o.request.done_s - (res.t0 + o.arrival.due_s)
                for o in res.offered]
    assert [round(x, 6) for x in from_due] == [1.0, 1.9, 2.8]
    from_submit = [o.request.done_s - o.request.submit_s for o in res.offered]
    assert [round(x, 6) for x in from_submit] == [1.0, 1.0, 2.0]
    assert res.window_s == 3.0 and round(res.drain_s, 6) == 2.8


def test_open_loop_hard_cut_and_refusals():
    clock = FakeClock()
    server = FakeServer(clock)
    refuse = server.try_submit

    def sometimes(prompt, n, seed=0):
        if seed == 1:
            return types.SimpleNamespace(admitted=False, request=None,
                                         reason="queue_full")
        return refuse(prompt, n, seed=seed)

    server.try_submit = sometimes
    prompt = np.ones(4, np.int32)
    sched = [loadgen.Arrival(0.1 * i, prompt, 2, i) for i in range(10)]
    res = loadgen.run_open_loop(server, sched, clock=clock, sleep=clock.sleep,
                                cut_s=2.5)
    assert 2.5 <= res.window_s < 3.6
    assert [o.error for o in res.offered if o.request is None] == ["queue_full"]
    assert sum(1 for o in res.offered
               if o.request is not None and o.request.done_s) == 3


def test_percentile_counts_missing_outcomes_as_infinite():
    inf = float("inf")
    assert loadgen.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    assert loadgen.percentile([1.0] * 99 + [inf], 95) == 1.0
    assert loadgen.percentile([1.0] * 90 + [inf] * 10, 95) == inf
