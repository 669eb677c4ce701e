"""Serve-side overload control: deadlines, criticality shedding, retry
budgets, hedging, and graceful drain.

The load-bearing claims, each asserted mechanically here:

1. **Deadlines shed at the earliest point.** An expired request is
   refused at admission, swept from the queue, or retired mid-flight —
   whichever comes first — and every shed decision leaves evidence
   (``shed_log`` + ``serve.shed`` tracer event + counters), split
   ``expired_in_queue`` vs ``expired_in_flight``.
2. **Criticality displacement never eats its own class.** At the queue
   bound an arrival may shed the costliest queued request of a STRICTLY
   lower class; an all-interactive overload sheds the newcomer, never a
   peer.
3. **Retries are budgeted.** Failover re-dispatch and hedges draw from
   per-class token buckets (``submitted * (1+ratio) + burst`` cap);
   a dry bucket parks the retry instead of amplifying the storm.
4. **Hedges are safe bets.** A tail-stuck interactive request races a
   second greedy copy; first winner cancels the loser, token-identical
   either way.
5. **Drain loses nothing.** ``FleetController.drain`` quiesces, stops
   the loop, migrates queued work and live KV slabs to survivors —
   zero recompute, zero lost tokens, no failover counter movement.
6. **The storm soak.** 3x-capacity Poisson load with a criticality mix
   and a mid-storm drain: interactive p50 TTFT holds within 2x the
   uncontended baseline, only batch/best-effort or past-deadline
   requests are shed, retry amplification stays under 1.2x, and the
   drained replica retires with zero lost tokens. This is the
   ``scripts/verify.sh --serve-slo`` gate.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import TransformerLM
from deeplearning4j_tpu.monitor import metrics, tracer
from deeplearning4j_tpu.monitor.trace import SpanTracer, set_tracer
from deeplearning4j_tpu.serving import (
    RetryBudget, DecodeServer, poisson_schedule)
from deeplearning4j_tpu.serving.scheduler import RequestQueue, ServeRequest
from deeplearning4j_tpu.serving.fleet import (
    FleetController, FleetLoadDriver, FleetRouter, ServeReplica)

_LM_CACHE = {}


def _lm(key="greedy", **kw):
    """One tiny model per config, cached for the module (same idiom as
    test_serving_fleet: many servers, one compile)."""
    if key not in _LM_CACHE:
        cfg = dict(vocab_size=61, d_model=32, num_heads=4,
                   num_kv_heads=2, num_layers=2, max_len=96, seed=3,
                   pos_encoding="rope")
        cfg.update(kw)
        _LM_CACHE[key] = TransformerLM(**cfg).init()
    return _LM_CACHE[key]


def _replica(rid, lm=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    return ServeReplica(rid, lm if lm is not None else _lm(), **kw)


def _ref(lm, prompt, n, **kw):
    return np.asarray(lm.generate(np.asarray(prompt)[None], n, **kw))[0]


def _server(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_queue", 4)
    return DecodeServer(_lm(), **kw)


def _prompt(n=4):
    return np.arange(1, n + 1, dtype=np.int32)


# ---------------------------------------------------------------------------
# deadlines: shed at the earliest point, with evidence
# ---------------------------------------------------------------------------
class TestDeadlineSheds:
    def test_expired_at_admission(self):
        server = _server()
        t = {"now": 10.0}
        server.clock = lambda: t["now"]
        v = server.try_submit(_prompt(), 4, deadline_s=9.0)
        assert not v.admitted and v.reason == "expired"
        assert server.shed_log[-1]["where"] == "admission"
        assert server.shed_log[-1]["reason"] == "deadline"
        assert server.shed[-1].state == "shed"
        # never cost a queue entry
        assert len(server.queue) == 0

    def test_expired_in_queue_swept_at_admit(self):
        server = _server(slots=1)
        t = {"now": 0.0}
        server.clock = lambda: t["now"]
        # fills the single slot
        v1 = server.try_submit(_prompt(), 2, deadline_s=100.0)
        server.step()
        # queued behind it with a tight deadline
        v2 = server.try_submit(_prompt(5), 4, deadline_s=0.5)
        assert v1.admitted and v2.admitted
        # expiry is observed at the pop — run the slot dry so admission
        # reaches the corpse rather than burning a prefill on it (the slot
        # is free once v1's last block is dispatched, a step before v1 is
        # read finished: the clock moves first)
        t["now"] = 1.0
        while v1.request.state != "finished":
            server.step()
        server.step()
        assert v2.request.state == "shed"
        assert v2.request.shed_reason == "deadline"
        assert server.stats()["expired_in_queue"] == 1
        assert server.stats()["expired_in_flight"] == 0

    def test_expired_in_flight_frees_slot(self):
        server = _server(slots=1)
        t = {"now": 0.0}
        server.clock = lambda: t["now"]
        v = server.try_submit(_prompt(), 8, deadline_s=0.5)
        server.step()                       # admitted + decoding
        assert v.request.state == "running"
        t["now"] = 1.0
        server.step()                       # sweep retires it
        assert v.request.state == "shed"
        assert server.stats()["expired_in_flight"] == 1
        # the freed slot takes new work immediately
        v2 = server.try_submit(_prompt(), 4, deadline_s=100.0)
        server.step()
        assert v2.request.state == "running"

    def test_env_deadline_budget_applies(self, monkeypatch):
        monkeypatch.setenv("DL4J_SERVE_DEADLINE_S", "2.5")
        server = _server()
        t = {"now": 100.0}
        server.clock = lambda: t["now"]
        v = server.try_submit(_prompt(), 4)
        assert v.admitted
        assert v.request.deadline_s == pytest.approx(102.5)

    def test_shed_events_on_tracer_timeline(self):
        tr = SpanTracer()
        set_tracer(tr)
        try:
            server = _server()
            t = {"now": 10.0}
            server.clock = lambda: t["now"]
            server.try_submit(_prompt(), 4, deadline_s=1.0)
            evs = [sp for sp in tr.spans() if sp.name == "serve.shed"]
            assert len(evs) == 1
            assert evs[0].attrs["reason"] == "deadline"
        finally:
            set_tracer(None)


# ---------------------------------------------------------------------------
# criticality displacement
# ---------------------------------------------------------------------------
class TestCriticalityDisplacement:
    def test_queue_pops_by_class_priority(self):
        q = RequestQueue(max_depth=4)
        reqs = [ServeRequest(prompt=_prompt(), max_new_tokens=4,
                             criticality=c)
                for c in ("batch", "best_effort", "interactive")]
        for r in reqs:
            assert q.try_push(r)
        assert q.pop() is reqs[2]           # interactive first
        assert q.pop() is reqs[0]           # then batch
        assert q.pop() is reqs[1]           # best_effort last

    def test_displace_sheds_costliest_of_lowest_class(self):
        q = RequestQueue(max_depth=2)
        cheap = ServeRequest(prompt=_prompt(2), max_new_tokens=2,
                             criticality="best_effort")
        costly = ServeRequest(prompt=_prompt(8), max_new_tokens=16,
                              criticality="best_effort")
        for r in (cheap, costly):
            assert q.try_push(r)
        newcomer = ServeRequest(prompt=_prompt(), max_new_tokens=4,
                                criticality="batch")
        admitted, victim = q.displace(newcomer)
        assert admitted and victim is costly

    def test_same_class_never_displaced(self):
        q = RequestQueue(max_depth=1)
        assert q.try_push(ServeRequest(prompt=_prompt(),
                                       max_new_tokens=4,
                                       criticality="batch"))
        admitted, victim = q.displace(
            ServeRequest(prompt=_prompt(), max_new_tokens=4,
                         criticality="batch"))
        assert not admitted and victim is None

    def test_server_displacement_evidence(self):
        server = _server(slots=1, max_queue=1)
        server.try_submit(_prompt(), 8, criticality="interactive")
        server.step()                       # slot taken
        vb = server.try_submit(_prompt(5), 4, criticality="batch")
        assert vb.admitted                  # fills the queue
        vi = server.try_submit(_prompt(6), 4, criticality="interactive")
        assert vi.admitted and vi.displaced is vb.request
        assert vb.request.state == "shed"
        assert vb.request.shed_reason == "shed_overload"
        decision = server.shed_log[-1]
        assert decision["reason"] == "shed_overload"
        assert decision["displaced_by"] == vi.request.id
        assert server.stats()["shed_by_class"] == {"batch": 1}

    def test_interactive_overload_sheds_newcomer_not_peer(self):
        server = _server(slots=1, max_queue=1)
        server.try_submit(_prompt(), 8, criticality="interactive")
        server.step()
        assert server.try_submit(_prompt(), 4,
                                 criticality="interactive").admitted
        v = server.try_submit(_prompt(), 4, criticality="interactive")
        assert not v.admitted and v.reason == "queue_full"
        assert server.stats()["shed"] == 0


# ---------------------------------------------------------------------------
# retry budget
# ---------------------------------------------------------------------------
class TestRetryBudget:
    def test_token_bucket_arithmetic(self):
        b = RetryBudget(ratio=0.5, burst=2.0)
        assert b.remaining("batch") == 2.0
        assert b.try_spend("batch") and b.try_spend("batch")
        assert not b.try_spend("batch")     # dry
        b.deposit("batch")
        assert b.remaining("batch") == pytest.approx(0.5)
        assert not b.has("batch")           # 0.5 < 1 token
        b.refund("batch", 5.0)
        assert b.remaining("batch") == 2.0  # capped at burst

    def test_classes_are_independent(self):
        b = RetryBudget(ratio=0.1, burst=1.0)
        assert b.try_spend("interactive")
        assert not b.has("interactive")
        assert b.has("batch")

    def test_unknown_class_rejected(self):
        b = RetryBudget()
        with pytest.raises(ValueError):
            b.deposit("platinum")

    def test_dry_budget_parks_failover_with_evidence(self):
        reps = [_replica(f"r{i}") for i in range(2)]
        router = FleetRouter(reps)
        router.retry_budget = RetryBudget(ratio=0.0, burst=0.0)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        frs = [router.submit(_prompt(), 4, seed=i) for i in range(2)]
        victim_rid = frs[0].replica_id
        victims = [fr for fr in frs if fr.replica_id == victim_rid]
        before = metrics().counter("serve_retry_denied_total").value(
            kind="failover", criticality="interactive")
        controller.evict(victim_rid, reason="test")
        # the re-dispatch was denied: parked, not placed, one evidence
        # record per request
        assert all(fr.replica_id is None for fr in victims)
        assert len(router._pending) == len(victims)
        assert metrics().counter("serve_retry_denied_total").value(
            kind="failover", criticality="interactive") \
            == before + len(victims)
        # funding the bucket lets the parked work place on the next tick
        router.retry_budget = RetryBudget(ratio=0.1, burst=10.0)
        assert router.retry_pending() == len(victims)
        survivor = [r for r in reps if r.alive][0]
        lm = _lm()
        while router.unfinished():
            survivor.step_once()
        for fr in frs:
            assert np.array_equal(fr.output,
                                  _ref(lm, fr.prompt, fr.max_new_tokens))

    def test_first_placement_is_free(self):
        reps = [_replica("r0")]
        router = FleetRouter(reps)
        router.retry_budget = RetryBudget(ratio=0.0, burst=0.0)
        fr = router.try_submit(_prompt(), 4)
        assert fr is not None and fr.replica_id == "r0"


# ---------------------------------------------------------------------------
# hedging
# ---------------------------------------------------------------------------
class TestHedging:
    def _fleet(self, t):
        reps = [_replica(f"r{i}", slots=1, max_queue=2)
                for i in range(2)]
        clock = lambda: t["now"]  # noqa: E731
        router = FleetRouter(reps, clock=clock)
        for r in reps:
            r.clock = clock
            r.server.clock = clock
        return reps, router

    def test_hedge_placed_after_threshold_and_budget_gated(self):
        t = {"now": 0.0}
        reps, router = self._fleet(t)
        router.hedge_after_s = 0.05
        # r0 and r1 each get a slot-filling request
        a = router.submit(_prompt(), 8, seed=0)
        b = router.submit(_prompt(5), 8, seed=0)
        for r in reps:
            r.step_once()
        # c queues behind one of them
        c = router.submit(_prompt(6), 4, seed=0)
        assert c.inner.state == "queued"
        assert router.maybe_hedge() == 0    # not past threshold yet
        t["now"] = 0.1
        assert router.maybe_hedge() == 1
        assert c.hedge is not None
        assert c.hedge_replica_id != c.replica_id
        assert len(router.hedge_log) == 1
        # a dry budget refuses further hedging
        router.retry_budget = RetryBudget(ratio=0.0, burst=0.0)
        c.hedge = None                      # pretend it never hedged
        c.hedge_replica_id = None
        assert router.maybe_hedge() == 0
        assert a is not None and b is not None

    def test_hedge_win_cancels_queued_primary(self):
        t = {"now": 0.0}
        reps, router = self._fleet(t)
        router.hedge_after_s = 0.05
        lm = _lm()
        a = router.submit(_prompt(), 2, seed=0)    # r0, short
        b = router.submit(_prompt(5), 8, seed=0)   # r1, long
        for r in reps:
            r.step_once()
        c = router.submit(_prompt(6), 4, seed=0)   # queued (on r0)
        primary_rid = c.replica_id
        t["now"] = 0.1
        assert router.maybe_hedge() == 1
        hedge_rep = router._by_id[c.hedge_replica_id]
        # the hedge's replica finishes its current stream, then starts
        # the hedge copy; the primary copy is STILL queued
        finish_first = a if hedge_rep.replica_id == "r0" else b
        while not finish_first.finished:
            hedge_rep.step_once()
        hedge_rep.step_once()
        assert c.hedge.state in ("running", "finished")
        assert c.inner.state == "queued"
        router.maybe_hedge()                # reconcile: hedge wins
        assert router.hedge_wins == 1
        assert c.replica_id == hedge_rep.replica_id
        assert c.hedge is None
        # the canceled primary no longer holds a seat on its old replica
        assert all(
            q is not c.inner
            for q in [router._by_id[primary_rid].server.queue.pop()])
        while not c.finished:
            hedge_rep.step_once()
        assert np.array_equal(c.output, _ref(lm, c.prompt, 4))

    def test_primary_win_cancels_hedge(self):
        t = {"now": 0.0}
        reps, router = self._fleet(t)
        router.hedge_after_s = 0.05
        router.submit(_prompt(), 8, seed=0)        # r0 busy
        router.submit(_prompt(5), 8, seed=0)       # r1 busy
        for r in reps:
            r.step_once()
        c = router.submit(_prompt(6), 4, seed=0)
        t["now"] = 0.1
        assert router.maybe_hedge() == 1
        hedge_req = c.hedge
        # the PRIMARY's replica frees first and starts c
        pri_rep = router._by_id[c.replica_id]
        while c.inner.state == "queued":
            pri_rep.step_once()
        router.maybe_hedge()                # reconcile: primary wins
        assert c.hedge is None and hedge_req.canceled
        assert router.hedge_wins == 0

    def test_sampled_fleet_refuses_hedging(self):
        lm = _lm("sampled", seed=4)
        reps = [ServeReplica(f"r{i}", lm, slots=1, max_len=64,
                             temperature=0.8) for i in range(2)]
        router = FleetRouter(reps)
        router.hedge_after_s = 0.0
        router.submit(_prompt(), 4, seed=7)
        assert router.maybe_hedge() == 0


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------
class TestDrain:
    def test_drain_migrates_live_and_queued_zero_recompute(self):
        lm = _lm()
        reps = [_replica(f"r{i}", slots=2, max_queue=4)
                for i in range(2)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        # 3 requests: two fill r0's slots, one queues behind them
        # (affinity pins them all to r0)
        frs = [router.submit(_prompt(4 + i), 8, seed=i, affinity="pin")
               for i in range(3)]
        assert all(fr.replica_id == "r0" for fr in frs)
        r0 = router._by_id["r0"]
        for _ in range(2):
            r0.step_once()                  # both live streams mid-flight
        live_tokens = {fr.id: list(fr.tokens) for fr in frs}
        assert any(live_tokens.values())    # some tokens already emitted
        failover_before = metrics().counter(
            "fleet_serve_failover_requests_total").value()
        decision = controller.drain("r0", reason="test-drain")
        # evidence + bookkeeping
        assert controller.drained == ["r0"]
        assert r0.retired and r0.alive is False and not r0.dead
        assert decision["migrated"] == 3
        assert decision["fallback_failovers"] == 0
        assert decision["live"] == 2 and decision["queued"] == 1
        assert controller.drain_log[-1] is decision
        # drain is NOT failover: the failover counter did not move
        assert metrics().counter(
            "fleet_serve_failover_requests_total").value() \
            == failover_before
        # already-emitted tokens were carried, not recomputed
        for fr in frs:
            assert list(fr.tokens)[:len(live_tokens[fr.id])] \
                == live_tokens[fr.id]
        r1 = router._by_id["r1"]
        while router.unfinished():
            r1.step_once()
        for fr in frs:
            assert np.array_equal(fr.output,
                                  _ref(lm, fr.prompt, fr.max_new_tokens))

    def test_drain_drops_hedge_copies_not_primaries(self):
        t = {"now": 0.0}
        reps = [_replica(f"r{i}", slots=1, max_queue=2)
                for i in range(2)]
        clock = lambda: t["now"]  # noqa: E731
        router = FleetRouter(reps, clock=clock)
        for r in reps:
            r.clock = clock
            r.server.clock = clock
        router.hedge_after_s = 0.05
        controller = FleetController(router, None, evict_timeout_s=5.0,
                                     clock=clock)
        router.submit(_prompt(), 8, seed=0)
        router.submit(_prompt(5), 8, seed=0)
        for r in reps:
            r.step_once()
        c = router.submit(_prompt(6), 4, seed=0)
        t["now"] = 0.1
        assert router.maybe_hedge() == 1
        hedge_rid = c.hedge_replica_id
        decision = controller.drain(hedge_rid, reason="test")
        assert decision["dropped_hedges"] == 1
        assert c.hedge is None
        assert not c.finished and c.shed_reason is None

    def test_drain_is_idempotent_and_skips_evicted(self):
        reps = [_replica(f"r{i}") for i in range(2)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        controller.drain("r0")
        assert controller.drain("r0")["reason"] == "already_evicted"
        controller.evict("r1", reason="dead")
        assert controller.drain("r1")["reason"] == "already_evicted"

    def test_drain_emits_flight_evidence(self):
        tr = SpanTracer()
        set_tracer(tr)
        try:
            reps = [_replica(f"r{i}") for i in range(2)]
            router = FleetRouter(reps)
            controller = FleetController(router, None,
                                         evict_timeout_s=5.0)
            router.submit(_prompt(), 4)
            controller.drain("r0")
            evs = [sp for sp in tr.spans() if sp.name == "serve.drain"]
            assert len(evs) == 1
            assert evs[0].attrs["replica"] == "r0"
        finally:
            set_tracer(None)


# ---------------------------------------------------------------------------
# the acceptance soak: 3x-capacity storm + mid-storm drain
# ---------------------------------------------------------------------------
class TestOverloadSoak:
    """Seeded virtual-clock storm at ~3x fleet capacity with a
    criticality mix, per-class deadlines, and a mid-storm graceful
    drain — the ``--serve-slo`` gate's assertions, read mechanically
    off the run report and the decision logs."""

    PIN = 0.005                             # pinned per-step cost

    def _fleet(self):
        reps = [_replica(f"r{i}", slots=2, max_queue=4)
                for i in range(3)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=50.0)

        def pinned_timer(replica):
            replica.step_once()
            return self.PIN

        return router, controller, FleetLoadDriver(
            router, controller, step_timer=pinned_timer)

    def test_storm_soak_slos(self):
        lm = _lm()
        # uncontended baseline: same fleet shape, gentle all-interactive
        # trickle — the TTFT yardstick
        _, _, base_driver = self._fleet()
        base_sched = poisson_schedule(
            30, rate_rps=20.0, vocab_size=61, prompt_lens=(4, 8),
            max_new_tokens=(6,), deadlines_s={"interactive": 10.0},
            seed=11)
        base = base_driver.run(base_sched).summary()
        assert base["finished"] == 30
        # uncontended TTFT on a virtual clock can round to zero (the
        # token lands in the same tick the request arrives); the
        # physical floor is two pinned steps, the one in flight when a
        # request arrives and the one that admits it
        base_ttft = max(base["ttft_p50_ms_by_class"]["interactive"],
                        2 * 1000.0 * self.PIN)

        # the storm: ~3x capacity. Capacity ~ 3 replicas x 2 slots x
        # (1 token / 0.005 s) / ~7 tokens-per-request ~ 170 rps;
        # drive 500 rps with a 25/60/15 class mix and per-class
        # deadline budgets wide enough that interactive holds
        router, controller, driver = self._fleet()
        sched = poisson_schedule(
            200, rate_rps=500.0, vocab_size=61, prompt_lens=(4, 8),
            max_new_tokens=(6,),
            criticality_mix={"interactive": 0.20, "batch": 0.65,
                             "best_effort": 0.15},
            deadlines_s={"interactive": 2.0, "batch": 0.15,
                         "best_effort": 0.08},
            seed=12)
        storm_len_s = sched[-1].arrival_s
        failover_before = metrics().counter(
            "fleet_serve_failover_requests_total").value()
        report = driver.run(sched, drain_at_s=storm_len_s / 2,
                            drain_replica="r0")
        s = report.summary()

        # --- the storm actually stormed, and deadlines actually fired
        assert s["shed"] + s["rejected"] > 0, s
        assert s["finished"] > 0
        assert s["expired_in_queue"] + s["expired_in_flight"] > 0, s

        # --- SLO 1: interactive p50 TTFT within 2x uncontended
        storm_ttft = s["ttft_p50_ms_by_class"]["interactive"]
        assert storm_ttft <= 2.0 * base_ttft, (storm_ttft, base_ttft)

        # --- SLO 2: every shed was batch/best_effort OR past-deadline
        decisions = list(router.shed_log)
        for r in router.replicas:
            decisions.extend(r.server.shed_log)
        assert decisions
        for d in decisions:
            assert (d["criticality"] in ("batch", "best_effort")
                    or d["reason"] == "deadline"), d

        # --- SLO 3: retry amplification bounded
        assert s["retry_amplification"] is not None
        assert s["retry_amplification"] <= 1.2, s["retry_amplification"]

        # --- SLO 4: the mid-storm drain retired r0 gracefully
        assert controller.drained == ["r0"]
        assert router._by_id["r0"].retired
        assert driver.drain_summary is not None
        assert driver.drain_summary["fallback_failovers"] == 0
        # zero recompute: the failover path never fired
        assert metrics().counter(
            "fleet_serve_failover_requests_total").value() \
            == failover_before

        # --- SLO 5: zero lost tokens — every finished stream is
        # token-identical to the uncontended reference (greedy fleet)
        finished = [fr for fr in router.requests if fr.finished]
        assert finished
        for fr in finished:
            assert np.array_equal(
                fr.output, _ref(lm, fr.prompt, fr.max_new_tokens)), fr.id

        # --- bookkeeping coherence: every submitted request ended in
        # exactly one terminal ledger column
        assert s["submitted"] == len(router.requests)
        states = [fr.state for fr in router.requests]
        assert s["finished"] + s["shed"] \
            + sum(1 for st in states
                  if st not in ("finished", "shed")) \
            == s["submitted"]
        # expiry split is consistent with the per-server evidence
        assert s["expired_in_queue"] + s["expired_in_flight"] \
            <= s["shed"] + len(router.shed_log)
