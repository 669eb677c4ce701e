"""Serve fleet: routing policy, failover token identity, controller
eviction + straggler flagging, prefill/decode handoff, the virtual-clock
fleet driver, and the replica-kill chaos round.

The load-bearing claims:

1. **Failover never costs tokens.** A killed/wedged replica's requests
   complete on survivors with output token-identical to an unfailed run
   — greedy streams continue from their emitted prefix (prompt+prefix
   re-prefilled; prefill is deterministic), sampled streams replay from
   the original seed (the RNG chain is a pure function of the seed).
2. **Handoffs are exact.** A prefill replica's exported
   ``(kv_slab, cursor, rng_key)`` installed into a decode replica's
   free slot produces the same stream a local prefill would — greedy
   AND sampled.
3. **Routing is least-loaded and bounded.** Free-slots-minus-queue
   headroom first, TTFT tiebreak, spill on full queues, drop only when
   every alive replica is full; in-flight streams never migrate.
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import TransformerLM
from deeplearning4j_tpu.monitor import metrics
from deeplearning4j_tpu.parallel.statetracker import InMemoryStateTracker
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.serving import (
    DecodeServer, ServeQueueFull, poisson_schedule, run_open_loop,
    serve_evict_s, serve_replicas, serve_role)
from deeplearning4j_tpu.serving.fleet import (
    FleetController, FleetLoadDriver, FleetRouter, ServeReplica,
    export_slot, install_slot, make_install)
from deeplearning4j_tpu.serving.fleet.handoff import SlotHandoff

_LM_CACHE = {}


def _lm(key="greedy", **kw):
    """One tiny model per config, cached for the module — fleet tests
    build many servers; the model (and its generate reference) should
    compile once."""
    if key not in _LM_CACHE:
        cfg = dict(vocab_size=61, d_model=32, num_heads=4,
                   num_kv_heads=2, num_layers=2, max_len=96, seed=3,
                   pos_encoding="rope")
        cfg.update(kw)
        _LM_CACHE[key] = TransformerLM(**cfg).init()
    return _LM_CACHE[key]


def _module_lm():
    """Latent attention, routed experts and a multi-token-prediction module
    the server drafts from: its decode block is a speculative round."""
    return _lm(
        "module", num_kv_heads=None, d_ff=16, norm="rmsnorm",
        tie_embeddings=False, num_experts=8, experts_per_token=2,
        norm_topk_prob=True, mixers=("mla",) * 2, ffns=("glu", "moe"),
        glu_width=32, mtp={"loss_weight": 0.3},
        mla={"q_lora_rank": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
             "qk_rope_head_dim": 8, "v_head_dim": 12, "gate": False})


def _replica(rid, lm=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    return ServeReplica(rid, lm if lm is not None else _lm(), **kw)


def _ref(lm, prompt, n, **kw):
    return np.asarray(lm.generate(np.asarray(prompt)[None], n, **kw))[0]


# ---------------------------------------------------------------------------
# env knobs
# ---------------------------------------------------------------------------
class TestEnvKnobs:
    def test_serve_replicas(self, monkeypatch):
        assert serve_replicas() == 2
        monkeypatch.setenv("DL4J_SERVE_REPLICAS", "5")
        assert serve_replicas() == 5
        monkeypatch.setenv("DL4J_SERVE_REPLICAS", "junk")
        assert serve_replicas() == 2

    def test_serve_role(self, monkeypatch):
        assert serve_role() == "mixed"
        monkeypatch.setenv("DL4J_SERVE_ROLE", "prefill")
        assert serve_role() == "prefill"
        monkeypatch.setenv("DL4J_SERVE_ROLE", "bogus")
        with pytest.raises(ValueError, match="DL4J_SERVE_ROLE"):
            serve_role()

    def test_serve_evict_s(self, monkeypatch):
        assert serve_evict_s() == 10.0
        monkeypatch.setenv("DL4J_SERVE_EVICT_S", "2.5")
        assert serve_evict_s() == 2.5

    def test_replica_rejects_unknown_role(self):
        with pytest.raises(ValueError, match="role"):
            _replica("r0", role="bogus")


# ---------------------------------------------------------------------------
# server hooks: try_submit verdicts + free_slot_count
# ---------------------------------------------------------------------------
class TestAdmissionVerdict:
    def test_try_submit_and_free_slots(self):
        server = DecodeServer(_lm(), slots=2, max_len=64, max_queue=1)
        assert server.free_slot_count() == 2
        v1 = server.try_submit(np.arange(1, 5, dtype=np.int32), 4)
        assert v1.admitted and v1.request is not None
        assert v1.reason is None
        # queue bound 1: the second queued submit is a verdict, not a
        # raise; submit() keeps the raising semantics unchanged
        v2 = server.try_submit(np.arange(1, 5, dtype=np.int32), 2)
        assert not v2.admitted and v2.reason == "queue_full"
        assert v2.request is None and v2.queue_depth == 1
        with pytest.raises(ServeQueueFull):
            server.submit(np.arange(1, 5, dtype=np.int32), 2)
        # admission moves the free-slot count at the step boundary
        server.step()
        assert server.free_slot_count() == 1
        server.drain()
        assert server.free_slot_count() == 2
        # malformed requests still raise (caller bugs, not load)
        with pytest.raises(ValueError):
            server.try_submit(np.zeros(0, np.int32), 2)
        with pytest.raises(ValueError):
            server.try_submit(np.arange(1, 5, dtype=np.int32), 999)

    def test_rejected_counter_on_verdict(self):
        reg = metrics()
        server = DecodeServer(_lm(), slots=1, max_len=64, max_queue=1)
        r0 = reg.counter("serve_requests_total").value(event="rejected")
        server.try_submit(np.arange(1, 4, dtype=np.int32), 2)
        v = server.try_submit(np.arange(1, 4, dtype=np.int32), 2)
        assert not v.admitted
        assert reg.counter("serve_requests_total").value(
            event="rejected") == r0 + 1


# ---------------------------------------------------------------------------
# loadgen: per-drop timestamps
# ---------------------------------------------------------------------------
class TestLoadgenDrops:
    def test_drop_timestamps_recorded(self):
        server = DecodeServer(_lm(), slots=1, max_len=64, max_queue=1,
                              clock=time.monotonic)
        # rate so hot the 1-slot/1-deep server must shed
        sched = poisson_schedule(8, rate_rps=5000.0, vocab_size=61,
                                 prompt_lens=(4,), max_new_tokens=(8,),
                                 seed=0)
        report = run_open_loop(server, sched)
        assert report.rejected > 0
        assert len(report.drop_times_s) == report.rejected
        assert report.submitted + report.rejected == 8
        s = report.summary()
        assert s["dropped_request_seconds"] == sorted(
            round(t, 3) for t in report.drop_times_s)
        # open-loop semantics kept: drops are not retried
        assert report.finished == report.submitted


# ---------------------------------------------------------------------------
# routing policy
# ---------------------------------------------------------------------------
class TestRouterPlacement:
    def test_least_loaded_splits_a_burst(self):
        reps = [_replica(f"r{i}") for i in range(2)]
        router = FleetRouter(reps)
        a = router.submit(np.arange(1, 5, dtype=np.int32), 2)
        b = router.submit(np.arange(1, 5, dtype=np.int32), 2)
        # headroom counts queued work: the second request of a burst
        # must go to the other replica even before any step boundary
        assert {a.replica_id, b.replica_id} == {"r0", "r1"}

    def test_ttft_tiebreak(self):
        reps = [_replica(f"r{i}") for i in range(2)]
        reps[0]._ttfts.append(0.5)    # slow history
        reps[1]._ttfts.append(0.01)   # fast history
        router = FleetRouter(reps)
        fr = router.submit(np.arange(1, 5, dtype=np.int32), 2)
        assert fr.replica_id == "r1"

    def test_spill_and_drop(self):
        reg = metrics()
        reps = [_replica(f"r{i}", slots=1, max_queue=1)
                for i in range(2)]
        router = FleetRouter(reps)
        placed = [router.try_submit(np.arange(1, 4, dtype=np.int32), 2)
                  for _ in range(2)]
        assert {fr.replica_id for fr in placed} == {"r0", "r1"}
        d0 = reg.counter("serve_route_total").value(outcome="dropped")
        # both queues at their bound: the fleet sheds, no exception
        assert router.try_submit(
            np.arange(1, 4, dtype=np.int32), 2) is None
        assert reg.counter("serve_route_total").value(
            outcome="dropped") == d0 + 1

    def test_sticky_affinity(self):
        reps = [_replica(f"r{i}", slots=4, max_queue=8)
                for i in range(2)]
        router = FleetRouter(reps)
        a = router.submit(np.arange(1, 5, dtype=np.int32), 2,
                          affinity="session-7")
        # load the OTHER replica so least-loaded would pick it — the
        # affinity pin must win anyway
        other = "r1" if a.replica_id == "r0" else "r0"
        b = router.submit(np.arange(1, 5, dtype=np.int32), 2,
                          affinity="session-7")
        assert b.replica_id == a.replica_id != other
        # a dead pinned replica falls back to least-loaded
        router._by_id[a.replica_id].dead = True
        c = router.submit(np.arange(1, 5, dtype=np.int32), 2,
                          affinity="session-7")
        assert c.replica_id == other

    def test_build_reads_env_replica_count(self, monkeypatch):
        monkeypatch.setenv("DL4J_SERVE_REPLICAS", "3")
        router = FleetRouter.build(_lm(), slots=2, max_len=64)
        assert [r.replica_id for r in router.replicas] == [
            "replica-0", "replica-1", "replica-2"]
        assert router.build(_lm(), replicas=1, slots=2,
                            max_len=64).stats()["replicas"] == 1

    def test_uniform_pool_config_required(self):
        small = _replica("r1", max_len=48)
        with pytest.raises(ValueError, match="max_len"):
            FleetRouter([_replica("r0"), small])

    def test_uniform_temperature_required(self):
        hot = _replica("r1", server=DecodeServer(
            _lm(), slots=2, max_len=64, temperature=0.8))
        with pytest.raises(ValueError, match="temperature"):
            FleetRouter([_replica("r0"), hot])


# ---------------------------------------------------------------------------
# failover
# ---------------------------------------------------------------------------
class TestFailover:
    def test_greedy_continuation_token_identity(self):
        lm = _lm()
        reps = [_replica(f"r{i}", slots=2) for i in range(2)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        prompt = np.arange(1, 7, dtype=np.int32)
        fr = router.submit(prompt, 8)
        victim = fr.replica_id
        router._by_id[victim].step_once()   # prefill + one dispatch
        emitted_before = len(fr.tokens)
        assert 0 < emitted_before < 8
        decision = controller.evict(victim, reason="test-kill")
        # the greedy continuation keeps the emitted prefix
        assert fr.emitted and len(fr.emitted) == emitted_before
        assert fr.replica_id != victim
        survivor = router._by_id[fr.replica_id]
        while survivor.busy():
            survivor.step_once()
        assert fr.finished
        assert np.array_equal(fr.output, _ref(lm, prompt, 8))
        # eviction evidence: decision in the log with the failover tally
        assert decision["replica"] == victim
        assert decision["failover"]["victims"] == 1
        assert controller.eviction_log[-1] is decision
        # the corpse's per-replica gauges are gone
        assert metrics().gauge("fleet_serve_occupancy").value(
            replica=victim) == 0.0

    def test_sampled_replay_token_identity(self):
        lm = _lm()
        reps = [ServeReplica(f"r{i}", lm, slots=2, max_len=64,
                             temperature=0.7, top_k=20)
                for i in range(2)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        prompt = np.arange(1, 7, dtype=np.int32)
        fr = router.submit(prompt, 6, seed=123)
        victim = fr.replica_id
        router._by_id[victim].step_once()
        assert fr.tokens  # partial progress existed
        controller.evict(victim, reason="test-kill")
        # sampled streams replay from scratch: the prefix is discarded
        # (the RNG chain cannot resume mid-stream) and the full replay
        # is identical because the chain is a pure function of the seed
        assert fr.emitted == []
        survivor = router._by_id[fr.replica_id]
        while survivor.busy():
            survivor.step_once()
        assert fr.finished
        assert np.array_equal(
            fr.output, _ref(lm, prompt, 6, temperature=0.7, top_k=20,
                            seed=123))

    def test_exact_dispatch_counts_across_failover(self):
        """The dryrun smoke's arithmetic, asserted here too: A needs 9
        (prefill 1 + 1 on r0 before the kill, then re-prefill emits 1 + 6
        steps on r1), B needs 5 (prefill 1 + 4 steps) — six dispatches on
        the survivor, the first four shared. A block is read one dispatch
        behind: r0's is held once ``flush()`` has read it, and a kill
        before that would have lost its token."""
        lm = _lm()
        reps = [_replica(f"f{i}", slots=2) for i in range(2)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        prompt = np.arange(1, 9, dtype=np.int32)
        fa = router.submit(prompt, 9)
        fb = router.submit(prompt + 1, 5)
        assert fa.replica_id == "f0" and fb.replica_id == "f1"
        reps[0].step_once()
        assert len(fa.tokens) == 1
        reps[0].server.flush()
        assert len(fa.tokens) == 2
        controller.evict("f0", reason="test-kill")
        while reps[1].busy():
            reps[1].step_once()
        assert fa.finished and fb.finished
        assert reps[0].server.steps == 1 and reps[1].server.steps == 6
        assert np.array_equal(fa.output, _ref(lm, prompt, 9))
        assert np.array_equal(fb.output, _ref(lm, prompt + 1, 5))

    @pytest.mark.parametrize("kind", ["plain", "module"])
    def test_a_kill_with_a_block_unread_costs_recompute_not_tokens(self,
                                                                   kind):
        """The same kill WITHOUT the ``flush()``: f0 dies with its one
        dispatched block unread — a plain step, or a round drafted
        from the model's own module — and that block's tokens die with
        it. A holds its prefill's token alone, re-prefills from prompt+1
        on the survivor (emitting 1) and decodes the 7 it still lacks
        there, beside B's 4: seven steps where the flushed kill
        needed six. Both streams are the reference's, token for token."""
        lm = _lm() if kind == "plain" else _module_lm()
        reps = [_replica(f"f{i}", lm, slots=2) for i in range(2)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        prompt = np.arange(1, 9, dtype=np.int32)
        fa = router.submit(prompt, 9)
        fb = router.submit(prompt + 1, 5)
        assert fa.replica_id == "f0" and fb.replica_id == "f1"
        while reps[0].server._unread is None:   # prefill, one dispatch
            reps[0].step_once()
        assert len(fa.tokens) == 1 and set(reps[0].server._unread[2]) == {0}
        controller.evict("f0", reason="test-kill")
        assert fa.emitted == fa.tokens and len(fa.tokens) == 1
        while reps[1].busy():
            reps[1].step_once()
        assert fa.finished and fb.finished
        # the unfailed run (``generate`` holds no latent rows: a server's)
        whole = DecodeServer(lm, slots=2, max_len=64)
        want = [whole.submit(prompt, 9), whole.submit(prompt + 1, 5)]
        whole.drain()
        assert np.array_equal(fa.output, want[0].output)
        assert np.array_equal(fb.output, want[1].output)
        if kind == "plain":
            assert np.array_equal(fa.output, _ref(lm, prompt, 9))
        assert reps[0].server.steps == 0        # its block was never read
        assert reps[1].server.decode_tokens == 7 + 4
        if kind == "plain":
            assert reps[1].server.steps == 7

    def test_fully_emitted_requeue_completes_without_survivor_work(self):
        """A max_new=1 split request whose handoff never installed: the
        prefill already emitted its one token, so eviction of the
        decode replica must complete the request in place — not strand
        it unfinished (the zero-lost contract) and not recompute it."""
        lm = _lm()
        pre = ServeReplica("p0", lm, role="prefill", slots=2,
                           max_len=64)
        dec = ServeReplica("d0", lm, role="decode", slots=2, max_len=64)
        router = FleetRouter([pre, dec])
        controller = FleetController(router, None, evict_timeout_s=5.0)
        prompt = np.arange(1, 6, dtype=np.int32)
        fr = router.submit(prompt, 1)
        pre.step_once()   # prefill done; handoff queued on d0, no step
        assert len(fr.tokens) == 1 and not fr.finished
        controller.evict("d0", reason="test-kill")
        assert fr.finished and fr.latency_s is not None
        assert np.array_equal(fr.output, _ref(lm, prompt, 1))

    def test_parked_failover_retries_when_survivor_frees(self):
        """Failover with every survivor full parks the victims; they
        must land (not be lost) once the survivor drains and the next
        tick retries."""
        lm = _lm()
        reps = [_replica(f"r{i}", slots=1, max_queue=1)
                for i in range(2)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        frs = [router.submit(np.arange(1, 5, dtype=np.int32), 4, seed=i)
               for i in range(2)]
        for r in reps:
            r.step_once()   # queued -> live; queues free up again
        frs += [router.submit(np.arange(1, 5, dtype=np.int32), 4,
                              seed=2 + i) for i in range(2)]
        victim = frs[0].replica_id
        survivor = router._by_id["r1" if victim == "r0" else "r0"]
        controller.evict(victim, reason="test-kill")   # 2 victims; the
        # survivor is full (1 live + 1 queued) so they park
        assert router.stats()["pending_failover"] > 0
        for _ in range(64):
            if not router.unfinished():
                break
            survivor.step_once()
            controller.tick()   # the retry site real-time fleets use
        assert all(fr.finished for fr in frs), [fr.state for fr in frs]
        for fr in frs:
            assert np.array_equal(fr.output, _ref(lm, fr.prompt, 4))

    def test_queued_requests_requeue_too(self):
        lm = _lm()
        reps = [_replica(f"r{i}", slots=1, max_queue=4)
                for i in range(2)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        frs = [router.submit(np.arange(1, 5, dtype=np.int32), 3, seed=i)
               for i in range(4)]
        victim = frs[0].replica_id
        controller.evict(victim, reason="test-kill")  # nothing stepped
        survivor = router._by_id[
            "r1" if victim == "r0" else "r0"]
        while survivor.busy():
            survivor.step_once()
        assert all(fr.finished for fr in frs)
        for fr in frs:
            assert np.array_equal(fr.output, _ref(lm, fr.prompt, 3))


# ---------------------------------------------------------------------------
# controller: gauges, stragglers, silence eviction
# ---------------------------------------------------------------------------
class TestController:
    def _fleet_of_three(self):
        # three replica handles over ONE shared server (cheap): the
        # controller only reads payloads in these tests, never steps
        shared = DecodeServer(_lm(), slots=2, max_len=64)
        reps = [ServeReplica(f"r{i}", _lm(), server=shared)
                for i in range(3)]
        return reps, FleetRouter(reps)

    def test_tick_gauges_from_payloads(self):
        reg = metrics()
        reps, router = self._fleet_of_three()
        controller = FleetController(router, None, evict_timeout_s=5.0)
        fleet = controller.tick()
        assert set(fleet) == {"r0", "r1", "r2"}
        assert reg.gauge("fleet_serve_replicas").value() == 3.0
        assert reg.gauge("fleet_serve_free_slots").value(
            replica="r1") == 2.0
        assert reg.gauge("fleet_serve_occupancy").value(
            replica="r2") == 0.0

    def test_straggler_flag_and_recovery(self):
        reg = metrics()
        reps, router = self._fleet_of_three()
        tracker = InMemoryStateTracker()
        controller = FleetController(router, tracker,
                                     evict_timeout_s=60.0,
                                     straggler_ratio=3.0)
        base = {"occupancy": 0.5, "queue_depth": 0, "free_slots": 1}
        tracker.heartbeat("r0", metrics={**base, "tpot_s": 0.01})
        tracker.heartbeat("r1", metrics={**base, "tpot_s": 0.012})
        tracker.heartbeat("r2", metrics={**base, "tpot_s": 0.2})
        c0 = reg.counter("fleet_serve_stragglers_total").value(
            replica="r2")
        controller.tick()
        assert controller.stragglers == {"r2"}
        assert reg.counter("fleet_serve_stragglers_total").value(
            replica="r2") == c0 + 1
        # recovery un-flags
        tracker.heartbeat("r2", metrics={**base, "tpot_s": 0.011})
        controller.tick()
        assert controller.stragglers == set()
        # below three reporting: no flags
        tracker2 = InMemoryStateTracker()
        tracker2.heartbeat("r0", metrics={**base, "tpot_s": 0.01})
        tracker2.heartbeat("r1", metrics={**base, "tpot_s": 9.9})
        controller2 = FleetController(router, tracker2,
                                      evict_timeout_s=60.0)
        controller2.tick()
        assert controller2.stragglers == set()

    def test_silence_eviction_with_evidence(self):
        reps, router = self._fleet_of_three()
        tracker = InMemoryStateTracker()
        controller = FleetController(router, tracker,
                                     evict_timeout_s=0.05)
        payload = {"occupancy": 1.0, "tpot_s": 0.02}
        for r in ("r0", "r1", "r2"):
            tracker.heartbeat(r, metrics=payload)
        time.sleep(0.08)
        tracker.heartbeat("r1", metrics=payload)
        tracker.heartbeat("r2", metrics=payload)
        controller.tick()
        assert controller.evicted == ["r0"]
        ev = controller.eviction_log[0]
        assert ev["reason"] == "heartbeat_silence"
        assert ev["silent_s"] >= 0.05
        assert ev["timeout_s"] == 0.05
        assert ev["last_metrics"]["occupancy"] == 1.0
        # an evicted replica is skipped by later ticks
        assert "r0" not in controller.tick()


# ---------------------------------------------------------------------------
# prefill/decode handoff
# ---------------------------------------------------------------------------
class TestHandoff:
    def test_export_install_round_trip_greedy(self):
        lm = _lm()
        import jax

        src = DecodeServer(lm, slots=2, max_len=64)
        dst = DecodeServer(lm, slots=2, max_len=64)
        prompt = np.arange(1, 9, dtype=np.int32)
        tok, key, _ = src.engine.prefill(prompt, 0, jax.random.PRNGKey(0))
        slabs = export_slot(src.engine, 0)
        handoff = SlotHandoff(slabs=slabs, cursor=len(prompt),
                              key=np.asarray(key), first_token=int(tok),
                              kv_dtype=src.engine.kv_dtype,
                              max_len=src.engine.max_len)
        from deeplearning4j_tpu.serving.scheduler import ServeRequest

        req = ServeRequest(prompt=prompt, max_new_tokens=6)
        req.submit_s = 0.0
        req.tokens.append(int(tok))
        dst.admit_external(req, make_install(handoff))
        assert dst.busy()
        dst.drain()
        assert req.state == "finished"
        out = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
        assert np.array_equal(out, _ref(lm, prompt, 6))

    def test_split_fleet_end_to_end_sampled(self):
        lm = _lm()
        pre = ServeReplica("p0", lm, role="prefill", slots=2,
                           max_len=64, temperature=0.7, top_k=20)
        dec = ServeReplica("d0", lm, role="decode", slots=2,
                           max_len=64, temperature=0.7, top_k=20)
        router = FleetRouter([pre, dec])
        assert router.split
        prompt = np.arange(1, 7, dtype=np.int32)
        fr = router.submit(prompt, 6, seed=42)
        assert fr.replica_id == "p0"
        pre.step_once()
        # prefill stamped TTFT and the router moved it to the decoder
        assert fr.replica_id == "d0"
        assert len(fr.tokens) == 1 and fr.ttft_s is not None
        while dec.busy():
            dec.step_once()
        assert fr.finished
        assert np.array_equal(
            fr.output,
            _ref(lm, prompt, 6, temperature=0.7, top_k=20, seed=42))

    def test_handoffs_of_array_prompts_park_side_by_side(self):
        """Three prefilled requests for a decode replica with one slot: the
        first is placed, the other two park and wait their turn. Requests
        are told apart by identity — their prompts are arrays, and a
        field-wise ``==`` between two parked ones raised ``ValueError``."""
        lm = _lm()
        pre = ServeReplica("p0", lm, role="prefill", slots=2, max_len=64)
        dec = ServeReplica("d0", lm, role="decode", slots=1, max_len=64)
        router = FleetRouter([pre, dec])
        prompts = [np.arange(i, i + 6, dtype=np.int32) for i in (1, 2, 3)]
        frs = [router.submit(p, 4) for p in prompts]
        assert all(isinstance(fr.prompt, np.ndarray) for fr in frs)
        while pre.busy():
            pre.step_once()
        assert frs[0].replica_id == "d0"
        assert router._pending == frs[1:] and frs[1] != frs[2]
        # parking one again holds it once
        assert not router.place_handoff(frs[2], frs[2]._parked_handoff)
        assert router._pending == frs[1:]
        for _ in range(40):
            if all(fr.finished for fr in frs):
                break
            dec.step_once()
            router.retry_pending()
        for fr, p in zip(frs, prompts):
            assert fr.finished and np.array_equal(fr.output, _ref(lm, p, 4))

    def test_split_fleet_config_and_capacity_validation(self):
        lm = _lm()
        # a speculative decode replica can never take handoffs: loud at
        # construction, not as a worker-thread death on first handoff
        pre = ServeReplica("p0", lm, role="prefill", slots=2,
                           max_len=64)
        spec_dec = ServeReplica("d0", _module_lm(), role="decode", server=(
            DecodeServer(_module_lm(), slots=2, max_len=64)))
        with pytest.raises(ValueError, match="speculative"):
            FleetRouter([pre, spec_dec])
        # oversized requests raise at submission like the mixed path,
        # instead of scattering past T_max on the decode side
        dec = ServeReplica("d0", lm, role="decode", slots=2, max_len=64)
        router = FleetRouter([pre, dec])
        with pytest.raises(ValueError, match="slot capacity"):
            router.submit(np.arange(1, 41, dtype=np.int32), 30)

    def test_handoff_validation(self):
        lm = _lm()
        import jax

        src = DecodeServer(lm, slots=2, max_len=64)
        prompt = np.arange(1, 5, dtype=np.int32)
        tok, key, _ = src.engine.prefill(prompt, 0, jax.random.PRNGKey(0))
        slabs = export_slot(src.engine, 0)

        def handoff(**kw):
            base = dict(slabs=slabs, cursor=4, key=np.asarray(key),
                        first_token=int(tok),
                        kv_dtype=src.engine.kv_dtype,
                        max_len=src.engine.max_len)
            base.update(kw)
            return SlotHandoff(**base)

        wrong_len = DecodeServer(lm, slots=2, max_len=48)
        with pytest.raises(ValueError, match="max_len"):
            install_slot(wrong_len.engine, 0, handoff())
        with pytest.raises(ValueError, match="kv_dtype"):
            install_slot(
                DecodeServer(lm, slots=2, max_len=64,
                             kv_dtype="bfloat16").engine, 0, handoff())
        # a hand-off carries no draft for a round to verify: reject
        spec = DecodeServer(_module_lm(), slots=2, max_len=64)
        from deeplearning4j_tpu.serving.scheduler import ServeRequest

        req = ServeRequest(prompt=prompt, max_new_tokens=2)
        req.tokens.append(int(tok))
        with pytest.raises(ValueError, match="speculative"):
            spec.admit_external(req, make_install(handoff()))
        # a request with no prefilled token is a protocol violation
        bare = ServeRequest(prompt=prompt, max_new_tokens=2)
        with pytest.raises(ValueError, match="prefilled"):
            DecodeServer(lm, slots=2, max_len=64).admit_external(
                bare, make_install(handoff()))


# ---------------------------------------------------------------------------
# virtual-clock driver
# ---------------------------------------------------------------------------
class TestVirtualDriver:
    def test_deterministic_scaling(self):
        """With a pinned per-step cost, 2 replicas under a saturating
        stream must finish in about half the single-replica wall — the
        arithmetic the bench's chip-per-replica model rides on."""
        def pinned_timer(replica):
            replica.step_once()
            return 0.01

        def run(n):
            reps = [_replica(f"r{i}", slots=2) for i in range(n)]
            router = FleetRouter(reps)
            driver = FleetLoadDriver(
                router, FleetController(router, None,
                                        evict_timeout_s=5.0),
                step_timer=pinned_timer)
            sched = poisson_schedule(12, rate_rps=1e4, vocab_size=61,
                                     prompt_lens=(4, 8),
                                     max_new_tokens=(6,), seed=5)
            report = driver.run(sched)
            assert report.finished == 12
            return report.summary()

        s1, s2 = run(1), run(2)
        scaling = s2["tokens_per_sec"] / s1["tokens_per_sec"]
        assert scaling > 1.6, scaling
        # queueing delay shrinks with capacity
        assert s2["p50_latency_ms"] < s1["p50_latency_ms"]

    def test_driver_failover_zero_lost(self):
        lm = _lm()

        def pinned_timer(replica):
            replica.step_once()
            return 0.01

        reps = [_replica(f"r{i}", slots=2) for i in range(2)]
        router = FleetRouter(reps)
        controller = FleetController(router, None, evict_timeout_s=5.0)
        driver = FleetLoadDriver(router, controller,
                                 step_timer=pinned_timer)
        sched = poisson_schedule(10, rate_rps=1e4, vocab_size=61,
                                 prompt_lens=(4,), max_new_tokens=(8,),
                                 seed=6)
        report = driver.run(sched, kill_at_s=0.02, kill_replica="r0")
        assert report.finished == 10  # zero lost
        assert controller.evicted == ["r0"]
        assert driver.kill_time_s is not None
        for fr in router.requests:
            assert np.array_equal(
                fr.output, _ref(lm, fr.prompt, fr.max_new_tokens))


# ---------------------------------------------------------------------------
# chaos: kill a live threaded replica mid-stream
# ---------------------------------------------------------------------------
@pytest.mark.chaos
class TestReplicaKillChaos:
    def test_replica_death_mid_stream_completes_on_survivors(self):
        """The satellite chaos round: real threads, real heartbeats, a
        ``DL4J_FAULTS``-style injected death of one replica while its
        requests are in flight — every request must complete on the
        survivor with greedy token identity vs an unfailed run, and the
        controller log must carry the eviction evidence."""
        lm = _lm()
        tracker = InMemoryStateTracker()
        reps = [ServeReplica(f"r{i}", lm, tracker=tracker,
                             heartbeat_interval_s=0.05, slots=2,
                             max_len=64)
                for i in range(2)]
        # warm the programs on this thread (jax tracing is not the
        # worker loop's job) and reset the bookkeeping
        for r in reps:
            r.server.submit(np.arange(1, 5, dtype=np.int32), 2)
            r.server.drain()
            r.server.finished.clear()
            r._finished_seen = 0
        router = FleetRouter(reps)
        controller = FleetController(router, tracker,
                                     evict_timeout_s=0.5)
        # queue the stream BEFORE the loops start, then kill r0 on its
        # 3rd loop iteration — it dies with work in flight
        frs = [router.submit(np.arange(1, 6, dtype=np.int32), 8, seed=i)
               for i in range(6)]
        on_r0 = [fr for fr in frs if fr.replica_id == "r0"]
        assert on_r0, "least-loaded routing should have used r0"
        try:
            faults.install("serve.replica.step.r0", faults.fail_nth(3))
            for r in reps:
                r.start()
            controller.start(interval_s=0.05)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if all(fr.finished for fr in frs):
                    break
                time.sleep(0.05)
        finally:
            faults.uninstall("serve.replica.step.r0")
            controller.stop()
            for r in reps:
                r.stop()
        assert all(fr.finished for fr in frs), [fr.state for fr in frs]
        assert reps[0].dead and "FaultInjected" in reps[0].dead_reason
        # zero lost + token identity (greedy) for EVERY request,
        # including the ones that failed over mid-stream
        for fr in frs:
            assert np.array_equal(fr.output, _ref(lm, fr.prompt, 8)), \
                fr.id
        evs = [e for e in controller.eviction_log
               if e["replica"] == "r0"]
        assert evs and evs[0]["reason"].startswith("crashed")
        assert evs[0]["failover"]["victims"] >= len(
            [fr for fr in on_r0 if fr.attempts > 1]) >= 0
