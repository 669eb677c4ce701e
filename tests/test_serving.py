"""Online serving subsystem: batched slot decode vs single-request
``generate`` equivalence, continuous batching, compile flatness, the
prompt-length ladder, the persisted compilation cache, the Poisson load
generator, and the direction-aware bench regression gate.

The load-bearing claims:

1. A slot's token sequence is IDENTICAL to ``TransformerLM.generate``
   on the same prompt — greedy and sampled (per-slot RNG replays the
   single-request ``split`` chain) — across learned/RoPE positions, GQA,
   sliding windows, bucket padding, and slot recycling.
2. The server compiles one decode program per slot count and one
   prefill per prompt-ladder rung, and a ragged stream adds ZERO
   programs after warmup.
3. ``generate_beam(beam_size=1)`` is greedy ``generate``.
"""

import functools
import importlib.util
import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import TransformerLM
from deeplearning4j_tpu.monitor import metrics, set_tracer, SpanTracer
from deeplearning4j_tpu.perf.bucketing import (
    DEFAULT_PROMPT_BUCKETS, pad_prompt, prompt_bucket)
from deeplearning4j_tpu.serving import (
    DecodeServer, ServeQueueFull, SlotKVCache, kv_pool_nbytes,
    max_slots_in_budget, poisson_schedule, run_open_loop,
    serve_draft_layers, serve_fuse_steps, serve_max_queue, serve_slots)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench_report():
    spec = importlib.util.spec_from_file_location(
        "bench_report_serving", os.path.join(REPO, "scripts",
                                             "bench_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench_report = _load_bench_report()


def _lm(pos_encoding="learned", **kw):
    cfg = dict(vocab_size=61, d_model=32, num_heads=4, num_kv_heads=2,
               num_layers=2, max_len=96, seed=3,
               pos_encoding=pos_encoding)
    cfg.update(kw)
    return TransformerLM(**cfg).init()


def _prompts(rng, lens, vocab=61):
    return [rng.integers(1, vocab, n).astype(np.int32) for n in lens]


class FakeClock:
    """Monotonic fake: every read advances ``tick`` so durations are
    nonzero and deterministic; ``sleep`` jumps the idle gaps."""

    def __init__(self, tick=0.01):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t

    def sleep(self, s):
        self.t += s


# ---------------------------------------------------------------------------
# prompt-length ladder (perf/bucketing.py satellite)
# ---------------------------------------------------------------------------
class TestPromptLadder:
    def test_rungs_are_smallest_upper_bound(self):
        assert prompt_bucket(1) == 16
        assert prompt_bucket(16) == 16
        assert prompt_bucket(17) == 32
        assert prompt_bucket(100) == 128

    def test_max_len_caps_the_rung(self):
        # 100 -> 128 would overflow a 120-slot pool: cap at max_len
        assert prompt_bucket(100, max_len=120) == 120
        assert prompt_bucket(100, max_len=4096) == 128

    def test_invalid_lengths_raise(self):
        with pytest.raises(ValueError):
            prompt_bucket(0)
        with pytest.raises(ValueError):
            prompt_bucket(130, max_len=120)

    def test_disable_flag_makes_prompts_exact(self, monkeypatch):
        monkeypatch.setenv("DL4J_DISABLE_BUCKETING", "1")
        assert prompt_bucket(13) == 13

    def test_pad_prompt_roundtrip(self):
        p = np.arange(1, 6, dtype=np.int32)
        padded, n = pad_prompt(p, 16)
        assert n == 5
        assert padded.shape == (16,)
        assert padded.dtype == np.int32
        assert np.array_equal(padded[:5], p)
        assert not padded[5:].any()

    def test_pad_prompt_batched_and_overflow(self):
        p = np.ones((2, 7), np.int32)
        padded, n = pad_prompt(p, 8)
        assert padded.shape == (2, 8) and n == 7
        with pytest.raises(ValueError):
            pad_prompt(np.ones(9, np.int32), 8)

    def test_ladder_stays_off_training_eval_paths(self):
        # the serving ladder is a separate constant: the batch ladder
        # the eval path uses must not silently grow prompt rungs
        from deeplearning4j_tpu.perf.bucketing import DEFAULT_BATCH_BUCKETS
        assert DEFAULT_PROMPT_BUCKETS != DEFAULT_BATCH_BUCKETS


# ---------------------------------------------------------------------------
# equivalence: batched slot decode vs single-request generate
# ---------------------------------------------------------------------------
class TestDecodeEquivalence:
    @pytest.mark.parametrize("pos_encoding", ["learned", "rope"])
    def test_greedy_matches_generate(self, rng, pos_encoding):
        """Three concurrent requests at ragged prompt/generation lengths
        through 2 slots (forces recycling) — token-for-token identical
        to the per-request ``generate`` programs."""
        lm = _lm(pos_encoding)
        prompts = _prompts(rng, (5, 11, 23))
        max_new = [7, 4, 9]
        refs = [np.asarray(lm.generate(p[None], m))[0]
                for p, m in zip(prompts, max_new)]
        srv = DecodeServer(lm, slots=2, max_len=96)
        reqs = [srv.submit(p, m) for p, m in zip(prompts, max_new)]
        srv.drain()
        for req, ref in zip(reqs, refs):
            assert req.state == "finished"
            assert np.array_equal(req.output, ref)

    def test_sampled_matches_generate_per_slot_rng(self, rng):
        """Each slot's RNG stream replays the single-request
        ``sample``/``split`` chain: serving with ``seed=s`` emits the
        same tokens as ``generate(..., seed=s)``."""
        lm = _lm(num_kv_heads=4)  # H == Hkv: the dense-attention path
        prompts = _prompts(rng, (5, 11))
        refs = [np.asarray(lm.generate(
            p[None], 6, temperature=0.7, top_k=13, seed=s))[0]
            for s, p in enumerate(prompts)]
        srv = DecodeServer(lm, slots=2, max_len=96, temperature=0.7,
                           top_k=13)
        reqs = [srv.submit(p, 6, seed=s) for s, p in enumerate(prompts)]
        srv.drain()
        for req, ref in zip(reqs, refs):
            assert np.array_equal(req.output, ref)

    def test_sliding_window_matches_generate(self, rng):
        lm = _lm("rope", attn_window=8)
        p = _prompts(rng, (13,))[0]
        ref = np.asarray(lm.generate(p[None], 10))[0]
        srv = DecodeServer(lm, slots=3, max_len=64)
        req = srv.submit(p, 10)
        srv.drain()
        assert np.array_equal(req.output, ref)

    def test_slot_recycling_preserves_tokens(self, rng):
        """6 requests through 2 slots: retired slots' stale K/V must be
        unreachable for their successors (the mask-correctness claim of
        the slot lifecycle)."""
        lm = _lm()
        prompts = _prompts(rng, (3, 9, 17, 5, 21, 7))
        max_new = [5, 2, 6, 8, 3, 4]
        refs = [np.asarray(lm.generate(p[None], m))[0]
                for p, m in zip(prompts, max_new)]
        srv = DecodeServer(lm, slots=2, max_len=96)
        reqs = [srv.submit(p, m) for p, m in zip(prompts, max_new)]
        srv.drain()
        for req, ref in zip(reqs, refs):
            assert np.array_equal(req.output, ref)

    def test_bucket_padding_is_mask_correct(self, rng, monkeypatch):
        """The same prompt served bucket-padded and exact produces the
        same tokens — the pad tail is causally unreachable."""
        lm = _lm("rope")
        p = _prompts(rng, (9,))[0]
        srv = DecodeServer(lm, slots=1, max_len=96)  # pads 9 -> 16
        req = srv.submit(p, 8)
        srv.drain()
        monkeypatch.setenv("DL4J_DISABLE_BUCKETING", "1")
        exact = DecodeServer(lm, slots=1, max_len=96)  # compiles at 9
        req2 = exact.submit(p, 8)
        exact.drain()
        assert exact.engine.compile_counts()["prefill_buckets"] == [9]
        assert np.array_equal(req.output, req2.output)

    def test_max_new_tokens_one_needs_no_decode_step(self, rng):
        lm = _lm()
        p = _prompts(rng, (6,))[0]
        ref = np.asarray(lm.generate(p[None], 1))[0]
        srv = DecodeServer(lm, slots=2, max_len=96)
        req = srv.submit(p, 1)
        srv.drain()
        assert np.array_equal(req.output, ref)
        assert srv.steps == 0  # retired at admission, no decode dispatch

    def test_beam_size_one_is_greedy_generate(self, rng):
        lm = _lm()
        prompt = np.stack(_prompts(rng, (7, 7)))
        greedy = np.asarray(lm.generate(prompt, 6))
        seqs, scores = lm.generate_beam(prompt, 6, beam_size=1)
        assert np.asarray(seqs).shape == (2, 1, 13)
        assert np.array_equal(np.asarray(seqs)[:, 0], greedy)


# ---------------------------------------------------------------------------
# continuous batching mechanics
# ---------------------------------------------------------------------------
class TestContinuousBatching:
    def test_compile_count_flat_after_warmup(self, rng):
        """A second ragged wave over the same ladder rungs adds ZERO
        programs — the acceptance invariant the bench asserts on-chip."""
        lm = _lm()
        srv = DecodeServer(lm, slots=3, max_len=96)
        before = metrics().counter("serve_program_builds_total").value(
            kind="prefill")
        for p, m in zip(_prompts(rng, (5, 12, 30)), (4, 3, 5)):
            srv.submit(p, m)
        srv.drain()
        warm = srv.engine.program_builds
        # decode, two prefill rungs, and the loop state's admission write
        assert srv.engine.compile_counts() == {
            "decode": 1, "prefill_buckets": [16, 32], "total": 4}
        assert metrics().counter("serve_program_builds_total").value(
            kind="prefill") == before + 2
        # steady state: same rung menu, different lengths/counts
        for p, m in zip(_prompts(rng, (7, 16, 25, 9)), (2, 5, 3, 4)):
            srv.submit(p, m)
        srv.drain()
        assert srv.engine.program_builds == warm
        assert len(srv.finished) == 7

    def test_queue_bound_rejects_with_backpressure(self, rng):
        lm = _lm()
        srv = DecodeServer(lm, slots=1, max_queue=2, max_len=96)
        reg = metrics()
        rejected0 = reg.counter("serve_requests_total").value(
            event="rejected")
        srv.submit(_prompts(rng, (4,))[0], 3)
        srv.submit(_prompts(rng, (4,))[0], 3)
        with pytest.raises(ServeQueueFull):
            srv.submit(_prompts(rng, (4,))[0], 3)
        assert reg.counter("serve_requests_total").value(
            event="rejected") == rejected0 + 1
        srv.drain()
        assert len(srv.finished) == 2

    def test_submit_validation(self, rng):
        lm = _lm()
        srv = DecodeServer(lm, slots=1, max_len=32)
        with pytest.raises(ValueError):
            srv.submit(np.empty(0, np.int32), 4)
        with pytest.raises(ValueError):
            srv.submit(_prompts(rng, (4,))[0], 0)
        with pytest.raises(ValueError):
            srv.submit(_prompts(rng, (30,))[0], 4)  # 34 > max_len

    def test_slot_capacity_validation(self):
        lm = _lm("learned")
        with pytest.raises(ValueError):
            SlotKVCache(lm, slots=0)
        with pytest.raises(ValueError):
            # learned table bounds the slot capacity the way it bounds
            # generate(); rope does not (second construction succeeds)
            SlotKVCache(lm, slots=2, max_len=200)
        rope = _lm("rope")
        assert SlotKVCache(rope, slots=2, max_len=200).max_len == 200

    def test_metrics_and_spans(self, rng):
        """TTFT/latency histograms, token counters, occupancy gauge,
        and the serve.step/serve.prefill spans all record."""
        lm = _lm()
        tr = SpanTracer()
        set_tracer(tr)
        try:
            reg = metrics()
            ttft0 = reg.histogram("serve_ttft_seconds").value()["count"]
            lat0 = reg.histogram(
                "serve_request_latency_seconds").value()["count"]
            tok0 = reg.counter("serve_tokens_total").value()
            srv = DecodeServer(lm, slots=2, max_len=96)
            reqs = [srv.submit(p, 4) for p in _prompts(rng, (5, 9))]
            srv.drain()
            assert all(r.ttft_s is not None and r.ttft_s >= 0
                       for r in reqs)
            assert all(r.latency_s is not None and r.latency_s >= 0
                       for r in reqs)
            assert reg.histogram("serve_ttft_seconds").value(
                )["count"] == ttft0 + 2
            assert reg.histogram("serve_request_latency_seconds").value(
                )["count"] == lat0 + 2
            assert reg.counter("serve_tokens_total").value() == tok0 + 8
            assert reg.gauge("serve_slot_occupancy").value() == 0.0
            names = {sp.name for sp in tr.spans()}
            assert {"serve.step", "serve.prefill"} <= names
            prefills = [sp for sp in tr.spans()
                        if sp.name == "serve.prefill"]
            assert {sp.attrs["prompt_len"] for sp in prefills} == {5, 9}
        finally:
            set_tracer(None)

    def test_env_defaults(self, monkeypatch):
        monkeypatch.setenv("DL4J_SERVE_SLOTS", "5")
        monkeypatch.setenv("DL4J_SERVE_MAX_QUEUE", "11")
        assert serve_slots() == 5
        assert serve_max_queue() == 11
        monkeypatch.setenv("DL4J_SERVE_SLOTS", "bogus")
        assert serve_slots() == 8
        monkeypatch.delenv("DL4J_SERVE_SLOTS")
        monkeypatch.delenv("DL4J_SERVE_MAX_QUEUE")
        assert serve_slots() == 8
        assert serve_max_queue() == 64


# ---------------------------------------------------------------------------
# Poisson open-loop load generator
# ---------------------------------------------------------------------------
class TestLoadGenerator:
    def test_schedule_is_deterministic_and_ragged(self):
        a = poisson_schedule(20, 50.0, vocab_size=61, seed=7)
        b = poisson_schedule(20, 50.0, vocab_size=61, seed=7)
        assert len(a) == 20
        assert all(x.arrival_s <= y.arrival_s for x, y in zip(a, a[1:]))
        assert {x.prompt.shape[0] for x in a} > {a[0].prompt.shape[0]}
        for x, y in zip(a, b):
            assert x.arrival_s == y.arrival_s
            assert np.array_equal(x.prompt, y.prompt)

    def test_open_loop_run_reports(self, rng):
        lm = _lm()
        clock = FakeClock()
        srv = DecodeServer(lm, slots=2, max_len=96, clock=clock)
        sched = poisson_schedule(
            8, 100.0, vocab_size=61, prompt_lens=(5, 9),
            max_new_tokens=(2, 4), seed=3)
        report = run_open_loop(srv, sched, clock=clock,
                               sleep=clock.sleep)
        s = report.summary()
        assert s["finished"] == 8 and s["rejected"] == 0
        assert s["tokens"] == sum(len(r.tokens) for r in srv.finished)
        assert s["p50_latency_ms"] > 0
        assert s["p99_latency_ms"] >= s["p50_latency_ms"]
        assert s["ttft_p50_ms"] > 0
        assert 0 < s["occupancy_mean"] <= 1
        assert s["tokens_per_sec"] > 0

    def test_open_loop_drops_on_overflow(self, rng):
        """Open loop means overflow drops — the stream must not turn
        into a closed loop behind the queue bound."""
        lm = _lm()
        clock = FakeClock(tick=0.001)
        srv = DecodeServer(lm, slots=1, max_queue=1, max_len=96,
                           clock=clock)
        # all arrivals at ~t=0: one runs, one queues, the rest reject
        sched = poisson_schedule(
            6, 1e6, vocab_size=61, prompt_lens=(5,),
            max_new_tokens=(6,), seed=0)
        report = run_open_loop(srv, sched, clock=clock,
                               sleep=clock.sleep)
        assert report.rejected > 0
        assert report.finished + report.rejected == 6
        assert report.finished == len(srv.finished)

    @pytest.mark.slow
    def test_soak_ragged_stream_never_recompiles(self, rng):
        """Soak: 60 ragged requests through 4 slots; after the first
        rung-covering wave the program count never moves, and every
        request finishes with exactly max_new tokens."""
        lm = _lm("rope")
        clock = FakeClock(tick=0.001)
        srv = DecodeServer(lm, slots=4, max_len=96, clock=clock)
        warm = poisson_schedule(
            8, 500.0, vocab_size=61, prompt_lens=(4, 12, 20, 40),
            max_new_tokens=(3, 6), seed=1)
        run_open_loop(srv, warm, clock=clock, sleep=clock.sleep)
        builds = srv.engine.program_builds
        soak = poisson_schedule(
            60, 500.0, vocab_size=61, prompt_lens=(4, 12, 20, 40),
            max_new_tokens=(3, 6), seed=2)
        report = run_open_loop(srv, soak, clock=clock, sleep=clock.sleep)
        assert srv.engine.program_builds == builds
        assert report.finished == 60
        for req in srv.finished:
            assert len(req.tokens) == req.max_new_tokens


# ---------------------------------------------------------------------------
# direction-aware bench regression gate (scripts/bench_report.py)
# ---------------------------------------------------------------------------
class TestBenchReportDirections:
    def test_latency_rise_is_a_regression(self):
        series = {"serve_p50_latency_ms": [(1, 100.0), (2, 150.0)]}
        out = bench_report.find_regressions(series, 20.0)
        assert len(out) == 1 and "above" in out[0]

    def test_latency_drop_is_an_improvement(self):
        series = {"serve_p99_latency_ms": [(1, 100.0), (2, 60.0)]}
        assert bench_report.find_regressions(series, 20.0) == []

    def test_throughput_direction_unchanged(self):
        assert bench_report.find_regressions(
            {"serve_tokens_per_sec": [(1, 100.0), (2, 70.0)]}, 20.0)
        assert not bench_report.find_regressions(
            {"serve_tokens_per_sec": [(1, 100.0), (2, 130.0)]}, 20.0)

    def test_lower_best_baseline_is_the_min(self):
        # r1's 80 is the best earlier point, not r2's 200: a 100 latest
        # is 25% above it -> regression even though it beats r2
        series = {"serve_p50_latency_ms": [(1, 80.0), (2, 200.0),
                                           (3, 100.0)]}
        out = bench_report.find_regressions(series, 20.0)
        assert len(out) == 1 and "r01" in out[0]

    def _write_round(self, path, n, serve):
        row = {"metric": "m", "value": 100.0, "unit": "u",
               "extras": {"serve": serve}}
        path.write_text(json.dumps({"n": n, "rc": 0, "parsed": row}))

    def test_end_to_end_gate_on_serve_section(self, tmp_path, capsys):
        a = tmp_path / "BENCH_r01.json"
        b = tmp_path / "BENCH_r02.json"
        self._write_round(a, 1, {"p50_latency_ms": 10.0,
                                 "p99_latency_ms": 20.0,
                                 "ttft_p50_ms": 5.0,
                                 "tokens_per_sec": 1000.0})
        self._write_round(b, 2, {"p50_latency_ms": 30.0,
                                 "p99_latency_ms": 21.0,
                                 "ttft_p50_ms": 5.0,
                                 "tokens_per_sec": 1000.0})
        rc = bench_report.main(["--check", str(a), str(b)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "serve_p50_latency_ms" in out
        assert "serve_p99_latency_ms" not in out  # 5% rise, under 20%

    def test_json_mode_carries_directions(self, tmp_path, capsys):
        a = tmp_path / "BENCH_r01.json"
        self._write_round(a, 1, {"p50_latency_ms": 10.0,
                                 "tokens_per_sec": 500.0})
        rc = bench_report.main(["--json", str(a)])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert payload["directions"]["serve_p50_latency_ms"] == "lower"
        assert payload["directions"]["serve_tokens_per_sec"] == "higher"
        row = payload["rounds"][0]
        assert row["serve_p50_latency_ms"] == 10.0


# ---------------------------------------------------------------------------
# fused multi-token decode: ("decode_fused", S, K)
# ---------------------------------------------------------------------------
class TestFusedDecode:
    @pytest.mark.parametrize("pos_encoding", ["learned", "rope"])
    def test_fused_greedy_token_identical(self, rng, pos_encoding):
        """K=4 fused decode over 2 slots with recycling across fusion
        boundaries — token-for-token identical to the K=1 path (which
        PR 10 pinned to ``generate``)."""
        lm = _lm(pos_encoding)
        prompts = _prompts(rng, (5, 11, 23))
        max_new = [7, 4, 9]
        refs = [np.asarray(lm.generate(p[None], m))[0]
                for p, m in zip(prompts, max_new)]
        srv = DecodeServer(lm, slots=2, max_len=96, fuse_steps=4)
        reqs = [srv.submit(p, m) for p, m in zip(prompts, max_new)]
        srv.drain()
        for req, ref in zip(reqs, refs):
            assert req.state == "finished"
            assert np.array_equal(req.output, ref)

    def test_fused_dispatch_count_is_ceil(self, rng):
        """The acceptance invariant: one request generating N tokens at
        fuse_steps=K takes exactly ceil((N - prefill_token)/K) decode
        dispatches, counter-asserted."""
        lm = _lm()
        p = _prompts(rng, (6,))[0]
        for k, max_new in ((4, 10), (3, 10), (5, 6), (4, 5)):
            srv = DecodeServer(lm, slots=1, max_len=96, fuse_steps=k)
            reg = metrics()
            d0 = reg.counter("serve_decode_steps_total").value()
            req = srv.submit(p, max_new)
            srv.drain()
            want = -(-(max_new - 1) // k)   # ceil; 1 token from prefill
            assert srv.steps == want, (k, max_new, srv.steps)
            assert reg.counter("serve_decode_steps_total").value() \
                == d0 + want
            assert np.array_equal(
                req.output, np.asarray(lm.generate(p[None], max_new))[0])

    def test_fused_sampled_matches_single_step(self, rng):
        """Per-slot RNG splits move in-program: the K=3 fused stream
        emits the same sampled tokens as ``generate(seed=s)``."""
        lm = _lm(num_kv_heads=4)
        prompts = _prompts(rng, (5, 11))
        refs = [np.asarray(lm.generate(
            p[None], 6, temperature=0.7, top_k=13, seed=s))[0]
            for s, p in enumerate(prompts)]
        srv = DecodeServer(lm, slots=2, max_len=96, fuse_steps=3,
                           temperature=0.7, top_k=13)
        reqs = [srv.submit(p, 6, seed=s) for s, p in enumerate(prompts)]
        srv.drain()
        for req, ref in zip(reqs, refs):
            assert np.array_equal(req.output, ref)

    def test_ragged_retirement_mid_scan(self, rng):
        """A short request (2 tokens) rides a K=4 scan beside a long one
        (9): the short slot self-freezes mid-scan (its remaining hits 0)
        and both streams stay token-exact through the recycle that
        follows."""
        lm = _lm()
        prompts = _prompts(rng, (4, 8, 6))
        max_new = [2, 9, 5]
        refs = [np.asarray(lm.generate(p[None], m))[0]
                for p, m in zip(prompts, max_new)]
        srv = DecodeServer(lm, slots=2, max_len=96, fuse_steps=4)
        reqs = [srv.submit(p, m) for p, m in zip(prompts, max_new)]
        srv.drain()
        for req, ref in zip(reqs, refs):
            assert len(req.tokens) == req.max_new_tokens
            assert np.array_equal(req.output, ref)

    def test_fuse_steps_one_is_pr10_bitwise(self, rng):
        """``DL4J_SERVE_FUSE_STEPS=1`` (the default) runs the identical
        PR-10 single-step program — same ("decode", S) cache key, same
        per-step dispatch cadence, same tokens."""
        lm = _lm()
        prompts = _prompts(rng, (5, 11))
        refs = [np.asarray(lm.generate(p[None], m))[0]
                for p, m in zip(prompts, (6, 4))]
        srv = DecodeServer(lm, slots=2, max_len=96)
        assert srv.fuse_steps == 1
        reqs = [srv.submit(p, m) for p, m in zip(prompts, (6, 4))]
        srv.drain()
        assert ("decode", 2) in srv.engine._programs
        assert not any(s[0] in ("decode_fused", "decode_spec")
                       for s in srv.engine._programs)
        assert srv.steps == 5   # max(6,4)-1: one dispatch per token
        for req, ref in zip(reqs, refs):
            assert np.array_equal(req.output, ref)
        assert srv.stats()["tokens_per_slot_dispatch"] == 1.0

    def test_fused_env_flag(self, rng, monkeypatch):
        monkeypatch.setenv("DL4J_SERVE_FUSE_STEPS", "4")
        assert serve_fuse_steps() == 4
        lm = _lm()
        srv = DecodeServer(lm, slots=1, max_len=96)
        assert srv.fuse_steps == 4
        monkeypatch.setenv("DL4J_SERVE_FUSE_STEPS", "bogus")
        assert serve_fuse_steps() == 1
        monkeypatch.delenv("DL4J_SERVE_FUSE_STEPS")
        assert serve_fuse_steps() == 1

    def test_fused_compile_flat_after_warmup(self, rng):
        """The fused program joins the bounded program set: a second
        ragged wave at the same (S, K) adds ZERO programs."""
        lm = _lm()
        srv = DecodeServer(lm, slots=3, max_len=96, fuse_steps=4)
        for p, m in zip(_prompts(rng, (5, 12, 30)), (4, 3, 5)):
            srv.submit(p, m)
        srv.drain()
        warm = srv.engine.program_builds
        assert ("decode_fused", 3, 4) in srv.engine._programs
        for p, m in zip(_prompts(rng, (7, 16, 25, 9)), (2, 5, 3, 4)):
            srv.submit(p, m)
        srv.drain()
        assert srv.engine.program_builds == warm

    def test_admission_waits_for_fusion_boundary(self, rng):
        """With fuse_steps=K a request submitted while a dispatch is in
        flight joins at the next step() — the admission-boundary
        semantics (queue drains only through _admit)."""
        lm = _lm()
        srv = DecodeServer(lm, slots=2, max_len=96, fuse_steps=4)
        srv.submit(_prompts(rng, (5,))[0], 9)
        srv.step()                     # dispatch in flight for req 1
        late = srv.submit(_prompts(rng, (7,))[0], 3)
        assert late.state == "queued"  # mid-flight: not admitted
        srv.step()                     # boundary: admitted + decoded
        assert late.state in ("running", "finished")
        srv.drain()
        assert np.array_equal(
            late.output,
            np.asarray(lm.generate(late.prompt[None], 3))[0])


# ---------------------------------------------------------------------------
# quantized KV pool (DL4J_SERVE_KV_DTYPE)
# ---------------------------------------------------------------------------
class TestQuantizedKV:
    def test_int8_pool_shrinks_4x(self):
        lm = _lm()
        f32 = SlotKVCache(lm, slots=4, max_len=96, kv_dtype="float32")
        i8 = SlotKVCache(lm, slots=4, max_len=96, kv_dtype="int8")
        ratio = f32.per_slot_nbytes / i8.per_slot_nbytes
        assert 3.5 < ratio <= 4.0, ratio
        assert kv_pool_nbytes(lm, 4, 96, "int8") == i8.nbytes
        assert kv_pool_nbytes(lm, 4, 96, "float32") == f32.nbytes

    def test_validate_cache_budget_prices_the_quantized_pool(self):
        """PR 8's budget validator sees the pool + scale sidecars the
        runtime actually allocated: predicted nbytes == measured device
        bytes, and the int8 pool measures ~4x under float32."""
        from deeplearning4j_tpu.monitor.memory import validate_cache_budget
        lm = _lm()
        out = {}
        for dt in ("float32", "int8"):
            cache = SlotKVCache(lm, slots=4, max_len=96, kv_dtype=dt)
            v = validate_cache_budget(cache)
            assert v["within_tolerance"], v
            assert v["predicted_per_shard_bytes"] \
                == v["measured_per_device_bytes"] == cache.nbytes
            out[dt] = v["measured_per_device_bytes"]
        assert 3.5 < out["float32"] / out["int8"] <= 4.0

    def test_max_slots_in_budget_multiplies(self):
        lm = _lm()
        budget = 64 * 1024 * 1024
        n_f32 = max_slots_in_budget(lm, 96, budget, "float32")
        n_i8 = max_slots_in_budget(lm, 96, budget, "int8")
        assert n_i8 > 3 * n_f32
        assert max_slots_in_budget(lm, 96, 0, "int8") == 0

    def test_kv_dtype_validation_and_env(self, monkeypatch):
        lm = _lm()
        with pytest.raises(ValueError):
            SlotKVCache(lm, slots=1, kv_dtype="int4")
        monkeypatch.setenv("DL4J_SERVE_KV_DTYPE", "bf16")
        assert SlotKVCache(lm, slots=1).kv_dtype == "bfloat16"
        monkeypatch.delenv("DL4J_SERVE_KV_DTYPE")
        # unset: the pool stays in the model's compute dtype (the
        # pre-quantization default, bitwise)
        assert SlotKVCache(lm, slots=1).kv_dtype == "float32"

    def test_int8_greedy_token_parity(self, rng):
        """End-to-end: the int8-quantized pool reproduces the
        full-precision greedy stream on the small test model (pinned
        prompts — int8 is lossy by design; the logit-error test bounds
        how lossy)."""
        lm = _lm()
        prompts = _prompts(rng, (5, 17))
        max_new = [7, 6]
        refs = [np.asarray(lm.generate(p[None], m))[0]
                for p, m in zip(prompts, max_new)]
        srv = DecodeServer(lm, slots=2, max_len=96, kv_dtype="int8")
        reqs = [srv.submit(p, m) for p, m in zip(prompts, max_new)]
        srv.drain()
        for req, ref in zip(reqs, refs):
            assert np.array_equal(req.output, ref)
        assert srv.stats()["kv_dtype"] == "int8"

    def test_int8_fused_matches_single_step(self, rng):
        """Quantization composes with fusion: K=3 int8 == K=1 int8
        token-for-token (the requant/scatter sequence per slot is the
        same op chain either way)."""
        lm = _lm("rope")
        prompts = _prompts(rng, (3, 9, 17, 5))
        max_new = [5, 2, 6, 8]
        a = DecodeServer(lm, slots=2, max_len=96, kv_dtype="int8")
        b = DecodeServer(lm, slots=2, max_len=96, kv_dtype="int8",
                         fuse_steps=3)
        ra = [a.submit(p, m) for p, m in zip(prompts, max_new)]
        a.drain()
        rb = [b.submit(p, m) for p, m in zip(prompts, max_new)]
        b.drain()
        for x, y in zip(ra, rb):
            assert np.array_equal(x.output, y.output)

    def test_int8_roundtrip_logit_error_bound(self):
        """The quantization error contract: a dequantized K/V element
        sits within absmax/127 of the original (half a quantum after
        rounding), including after a requantizing scale growth."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.serving.kv_cache import (
            dequant_slab, requant_write_slab)

        rng = np.random.default_rng(7)
        s_, t_, h_, d_ = 3, 8, 2, 4
        slab = jnp.zeros((s_, t_, h_, d_), jnp.int8)
        scale = jnp.zeros((s_, h_), jnp.float32)
        rows = jnp.arange(s_)
        vals1 = jnp.asarray(rng.normal(size=(s_, 4, h_, d_)), jnp.float32)
        pos1 = jnp.tile(jnp.arange(4)[None], (s_, 1))
        slab, scale = requant_write_slab(slab, scale, vals1, rows, pos1)
        # second write with LARGER values: forces a requantization of
        # the first write's entries under the grown scale
        vals2 = 3.0 * jnp.asarray(
            rng.normal(size=(s_, 4, h_, d_)), jnp.float32)
        pos2 = pos1 + 4
        slab, scale = requant_write_slab(slab, scale, vals2, rows, pos2)
        deq = np.asarray(dequant_slab(slab, scale, jnp.float32))
        bound = np.asarray(scale)[:, None, :, None] / 127.0 + 1e-7
        err1 = np.abs(deq[:, :4] - np.asarray(vals1))
        err2 = np.abs(deq[:, 4:] - np.asarray(vals2))
        # the requantized first write pays one extra rounding: 2 quanta
        assert (err1 <= 2 * bound).all(), err1.max()
        assert (err2 <= bound).all(), err2.max()


# ---------------------------------------------------------------------------
# speculative decoding (draft + verify inside the fused program)
# ---------------------------------------------------------------------------
class TestSpeculativeDecode:
    def test_full_self_draft_accepts_everything(self, rng):
        """draft_layers == num_layers makes the draft the target: every
        proposal verifies, a round yields spec_tokens + 1 tokens, and the
        stream is the target's greedy stream."""
        lm = _lm()
        p = _prompts(rng, (5,))[0]
        srv = DecodeServer(lm, slots=1, max_len=96, draft_layers=2,
                           spec_tokens=3)
        req = srv.submit(p, 13)     # 12 decode tokens = 3 full rounds
        srv.drain()
        assert np.array_equal(
            req.output, np.asarray(lm.generate(p[None], 13))[0])
        st = srv.stats()
        assert st["spec_accept_rate"] == 1.0
        assert st["spec_emitted"] == 4 * st["spec_rounds"] == 12
        # read one dispatch behind, the host dispatches a fourth time on
        # "may owe a token" (12 - 4 - 4 less the one token the unread
        # round holds at least): the device had frozen the slot, and the
        # block says so
        assert (srv.steps, st["empty_dispatches"]) == (4, 1)
        assert st["tokens_per_slot_dispatch"] == 3.0

    @pytest.mark.parametrize("pos_encoding", ["learned", "rope"])
    def test_shallow_draft_greedy_token_identity(self, rng, pos_encoding):
        """The speculative contract: whatever the draft proposes (here a
        1-of-2-layer self-draft with a low accept rate), the emitted
        stream is EXACTLY the target model's greedy stream — acceptance
        only changes how many dispatches it takes."""
        lm = _lm(pos_encoding)
        prompts = _prompts(rng, (5, 11, 23))
        max_new = [7, 4, 9]
        refs = [np.asarray(lm.generate(p[None], m))[0]
                for p, m in zip(prompts, max_new)]
        srv = DecodeServer(lm, slots=2, max_len=96, draft_layers=1,
                           spec_tokens=3)
        reqs = [srv.submit(p, m) for p, m in zip(prompts, max_new)]
        srv.drain()
        for req, ref in zip(reqs, refs):
            assert np.array_equal(req.output, ref)
        st = srv.stats()
        assert st["speculative"] and st["spec_proposed"] > 0

    def test_provided_draft_model(self, rng):
        """An independently seeded draft TransformerLM rides the same
        slot machinery (its own pool) and preserves target greedy
        token identity."""
        lm = _lm("rope")
        draft = _lm("rope", num_layers=1, seed=9)
        p = _prompts(rng, (9,))[0]
        ref = np.asarray(lm.generate(p[None], 8))[0]
        srv = DecodeServer(lm, slots=2, max_len=96, draft_model=draft,
                           spec_tokens=2)
        req = srv.submit(p, 8)
        srv.drain()
        assert np.array_equal(req.output, ref)

    def test_spec_composes_with_fuse_steps(self, rng):
        """K rounds per dispatch: fuse_steps=2 x spec_tokens=2 emits up
        to 6 tokens per dispatch and stays target-greedy-exact."""
        lm = _lm()
        prompts = _prompts(rng, (3, 9, 17))
        refs = [np.asarray(lm.generate(p[None], 9))[0] for p in prompts]
        srv = DecodeServer(lm, slots=2, max_len=96, draft_layers=2,
                           spec_tokens=2, fuse_steps=2)
        reqs = [srv.submit(p, 9) for p in prompts]
        srv.drain()
        for req, ref in zip(reqs, refs):
            assert np.array_equal(req.output, ref)
        assert srv.stats()["tokens_per_slot_dispatch"] > 1.0

    def test_sampled_spec_matches_target_distribution(self):
        """Accept/resample correctness, statistically: the marginal of
        a decode-phase token under speculative sampling stays within a
        total-variation bound of the vanilla sampled server's (exact
        per-token identity is NOT expected — the RNG consumption
        differs; the DISTRIBUTION must not)."""
        V = 13
        lm = TransformerLM(vocab_size=V, d_model=16, num_heads=2,
                           num_layers=2, max_len=32, seed=5).init()
        prompt = np.array([1, 2, 3], np.int32)
        n = 300

        def freqs(**kw):
            srv = DecodeServer(lm, slots=1, max_len=32, temperature=0.9,
                               **kw)
            c = np.zeros(V)
            for s in range(n):
                req = srv.submit(prompt, 4, seed=s)
                srv.drain()
                c[req.tokens[2]] += 1
            return c / n

        ref = freqs()
        spec = freqs(draft_layers=1, spec_tokens=2)
        tv = 0.5 * np.abs(ref - spec).sum()
        assert tv < 0.15, tv

    def test_env_flag_and_validation(self, rng, monkeypatch):
        monkeypatch.setenv("DL4J_SERVE_DRAFT_LAYERS", "1")
        assert serve_draft_layers() == 1
        lm = _lm()
        srv = DecodeServer(lm, slots=1, max_len=96)
        assert srv.engine.spec
        assert srv.engine.draft_model.num_layers == 1
        monkeypatch.delenv("DL4J_SERVE_DRAFT_LAYERS")
        with pytest.raises(ValueError):
            DecodeServer(lm, slots=1, max_len=96, draft_layers=3)
        with pytest.raises(ValueError):
            DecodeServer(lm, slots=1, max_len=96, draft_layers=1,
                         spec_tokens=0)
        with pytest.raises(ValueError):
            # draft vocab mismatch
            DecodeServer(lm, slots=1, max_len=96,
                         draft_model=_lm(vocab_size=32))

    def test_spec_capacity_needs_verify_slack(self, rng):
        """The verify forward writes spec_tokens candidates past the
        live cursor: submit() reserves that slack against max_len."""
        lm = _lm()
        srv = DecodeServer(lm, slots=1, max_len=32, draft_layers=1,
                           spec_tokens=4)
        with pytest.raises(ValueError):
            srv.submit(_prompts(rng, (20,))[0], 9)   # 29 + 4 > 32
        req = srv.submit(_prompts(rng, (20,))[0], 8)  # 28 + 4 == 32
        srv.drain()
        assert len(req.tokens) == 8


# ---------------------------------------------------------------------------
# the decode-family programs update the donated pool in place (PR 24)
# ---------------------------------------------------------------------------
def _slab_step_reference(model, params, kv, toks, positions):
    """The decode-family forward as it was before PR 24, kept here as the
    reference: each layer's ``[S, T, Hkv, Dh]`` slab is sliced out of the
    pool, ``requant_write_slab`` scatters into the copy, attention reads
    it, and the slabs are stacked back into a fresh pool. ``toks`` and
    ``positions`` are ``[S, Q]``. Returns ``(logits [S, Q, V], pool)``."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.attention import grouped_query_attention
    from deeplearning4j_tpu.serving.kv_cache import (
        dequant_slab, requant_write_slab)

    cdt = model.policy.compute_dtype
    t_max = kv["k"].shape[2]
    h = jnp.take(params["embed"], toks, axis=0)
    if model.pos_encoding == "learned":
        h = h + params["pos"][positions]
    h = model.policy.cast_compute(h)
    live = jnp.arange(t_max)[None, None, :] <= positions[:, :, None]
    if model.attn_window is not None:
        live &= (jnp.arange(t_max)[None, None, :]
                 > positions[:, :, None] - model.attn_window)
    rows = jnp.arange(toks.shape[0])
    out = {name: [] for name in kv}

    def cached_attention(li):
        def attn(q, kk, vv):
            slabs = []
            for name, new in (("k", kk), ("v", vv)):
                scale = kv.get(name + "_scale")
                slab, scale = requant_write_slab(
                    kv[name][li], None if scale is None else scale[li],
                    new, rows, positions)
                out[name].append(slab)
                if scale is not None:
                    out[name + "_scale"].append(scale)
                slabs.append(dequant_slab(slab, scale, cdt))
            return grouped_query_attention(q, *slabs, mask=live)
        return attn

    for li, blk in enumerate(params["blocks"]):
        h, _, _ = model._block(blk, h, attention=cached_attention(li),
                               positions=positions)
    return (model._unembed(params, h),
            {name: jnp.stack(slabs) for name, slabs in out.items()})


def _random_pool(rng, lm, slots, max_len, kv_dtype, scale):
    """A pool of seeded content (and, for int8, scales all ``scale``)."""
    import jax.numpy as jnp

    cache = SlotKVCache(lm, slots, max_len, kv_dtype)
    kv = {}
    for name, arr in cache.state.items():
        if name.endswith("_scale"):
            kv[name] = jnp.full(arr.shape, scale, arr.dtype)
        elif arr.dtype == jnp.int8:
            kv[name] = jnp.asarray(
                rng.integers(-127, 128, arr.shape), jnp.int8)
        else:
            kv[name] = jnp.asarray(rng.normal(size=arr.shape), arr.dtype)
    return kv


class TestInPlacePool:
    SLOTS, MAX_LEN = 3, 24

    @pytest.mark.parametrize("kind", ["decode", "decode_fused", "verify"])
    def test_program_aliases_the_pool(self, kind):
        """Compiled with the pool donated, a decode-family program's
        output pool IS its input pool (``alias_size_in_bytes``) and its
        temporaries stay under half a pool: before PR 24 the slabs were
        sliced out and stacked back, one whole pool of temporaries."""
        import functools

        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.serving import engine as eng

        # four layers: XLA:CPU still copies one layer's K and V slabs out
        # for the attention dots, a quarter of the pool
        slots, max_len = 8, 512
        lm = _lm("rope", max_len=max_len, num_layers=4)
        kv = SlotKVCache(lm, slots, max_len, "float32").state
        vec = jnp.zeros(slots, jnp.int32)
        keys = jnp.zeros((slots, 2), jnp.uint32)
        sampler = eng._row_sampler(0.0, None)
        if kind == "decode":
            fn = functools.partial(eng._serve_decode_impl, lm, sampler)
            args, donate = (lm.params, kv, vec, vec, keys), (1,)
        elif kind == "decode_fused":
            fn = functools.partial(eng._serve_decode_fused_impl, lm,
                                   sampler, 2)
            loop = {"cursors": vec, "tok": vec, "remaining": vec,
                    "keys": keys}
            args, donate = (lm.params, kv, loop), (1,)
        else:
            fn = functools.partial(eng._serve_verify_impl, lm)
            pos = jnp.zeros((slots, 3), jnp.int32)
            args, donate = (lm.params, kv, pos, pos), (1,)
        mem = jax.jit(fn, donate_argnums=donate).lower(
            *args).compile().memory_analysis()
        pool = sum(int(a.nbytes) for a in kv.values())
        donated = sum(int(leaf.nbytes) for i in donate
                      for leaf in jax.tree_util.tree_leaves(args[i]))
        assert mem.alias_size_in_bytes == donated >= pool
        assert mem.temp_size_in_bytes < pool // 2, (
            mem.temp_size_in_bytes, pool)

    @pytest.mark.parametrize("kv_dtype,scale", [
        ("float32", None), ("bfloat16", None),
        ("int8", 0.0),      # every write's absmax grows the scale
        ("int8", 1e3),      # no write does: the scatter-only branch
    ])
    @pytest.mark.parametrize("queries", [1, 3])
    def test_bitwise_equal_to_the_slab_formulation(self, rng, kv_dtype,
                                                   scale, queries):
        """Scattering into the pool and attending to ``pool[li]`` is the
        old slice / scatter / stack step bit for bit: logits and the
        returned pool, one query a slot (decode) and several (verify).
        Slot 1 is frozen past ``T_max``: its write is dropped and, unless
        a grown int8 scale requantizes them, its rows come back
        untouched."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.serving import engine as eng

        lm = _lm("rope", attn_window=16)
        kv = _random_pool(rng, lm, self.SLOTS, self.MAX_LEN, kv_dtype,
                          scale)
        first = jnp.asarray([5, self.MAX_LEN, self.MAX_LEN - queries])
        positions = first[:, None] + jnp.arange(queries)[None, :]
        toks = jnp.asarray(
            rng.integers(1, 61, (self.SLOTS, queries)), jnp.int32)
        want_logits, want_kv = _slab_step_reference(
            lm, lm.params, kv, toks, positions)
        if queries == 1:
            logits, new_kv = eng._decode_step_body(
                lm, lm.params, kv, toks[:, 0], positions[:, 0])
            logits = logits[:, None]
        else:
            logits, new_kv = eng._serve_verify_impl(
                lm, lm.params, kv, toks, positions)
        assert np.array_equal(np.asarray(logits), np.asarray(want_logits))
        assert sorted(new_kv) == sorted(want_kv)
        for name in kv:
            got = np.asarray(new_kv[name].astype(jnp.float32))
            assert np.array_equal(
                got, np.asarray(want_kv[name].astype(jnp.float32))), name
            if scale != 0.0:
                assert np.array_equal(
                    got[:, 1],
                    np.asarray(kv[name].astype(jnp.float32))[:, 1])
        if scale == 0.0:
            assert (np.asarray(new_kv["k_scale"])[:, 0] > 0).all()
        elif scale:
            assert (np.asarray(new_kv["k_scale"]) == scale).all()


# ---------------------------------------------------------------------------
# the plain decode loop: state on the device, read one step behind
# ---------------------------------------------------------------------------
class SyncLoop:
    """The straightforward loop the pipelined server is held to: FIFO
    admission into free slots, then ONE decode dispatch per step from HOST
    arrays (last tokens, cursors, keys, the live mask) through the one-step
    program body, one blocking read, the cursors advanced by the host —
    nothing but the pool kept on the device. Every request keeps its
    tokens, its routing rows and the key its stream held after each
    token."""

    def __init__(self, lm, slots, max_len, **engine_kw):
        import functools
        from collections import deque

        import jax
        from deeplearning4j_tpu.serving import engine as eng

        self.lm, self.slots = lm, slots
        self.engine = eng.DecodeEngine(lm, slots, max_len=max_len,
                                       **engine_kw)
        self.run = jax.jit(functools.partial(
            eng._serve_decode_impl, lm, self.engine._sample_row))
        self.tok = np.zeros(slots, np.int32)
        self.cursors = np.zeros(slots, np.int32)
        self.keys = np.zeros((slots, 2), np.uint32)
        self.req = [None] * slots
        self.queue = deque()

    def submit(self, prompt, max_new, seed=0):
        from types import SimpleNamespace

        req = SimpleNamespace(prompt=prompt, max_new=max_new, seed=seed,
                              tokens=[], keys=[], routing=[])
        self.queue.append(req)
        return req

    def _rows(self, packed):
        from deeplearning4j_tpu.serving.engine import unpack_routing

        return unpack_routing(np.asarray(packed), self.lm.num_experts,
                              self.lm.experts_per_token)[1:3]

    def _took(self, slot, req, tok, key, rows):
        req.tokens.append(int(tok))
        req.keys.append(np.asarray(key))
        if rows is not None:
            req.routing.append(rows)
        self.tok[slot], self.keys[slot] = tok, key
        self.req[slot] = req if len(req.tokens) < req.max_new else None

    def step(self):
        import jax
        import jax.numpy as jnp

        for slot in range(self.slots):
            if self.req[slot] is None and self.queue:
                req = self.queue.popleft()
                tok, key, packed = self.engine.prefill(
                    req.prompt, slot, jax.random.PRNGKey(req.seed))
                n = len(req.prompt)
                self.cursors[slot] = n
                self._took(slot, req, tok, key, None if packed is None else
                           tuple(a[:, :n] for a in self._rows(packed)))
        live = np.array([r is not None for r in self.req])
        if not live.any():
            return bool(self.queue)
        args = [self.lm.params, self.engine.cache.state,
                jnp.asarray(self.tok), jnp.asarray(self.cursors),
                jnp.asarray(self.keys)]
        if self.lm.num_experts:
            args.append(jnp.asarray(live))
        toks, keys, state, *packed = self.run(*args)
        self.engine.cache.install(state)
        toks, keys = np.asarray(toks), np.asarray(keys)
        rows = self._rows(packed[0]) if packed else None
        for slot in np.flatnonzero(live):
            self._took(slot, self.req[slot], toks[slot], keys[slot],
                       None if rows is None else
                       tuple(a[:, slot:slot + 1] for a in rows))
            self.cursors[slot] += 1
        return True

    def drain(self):
        while self.step():
            pass


class ManualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _routed_lm():
    """OLMoE's block, tiny: RMSNorm, QK-norm, untied head, RoPE, 4 SwiGLU
    experts with 2 a token (float32: a row depends on that row alone, bit
    for bit, whatever the batch holds)."""
    return TransformerLM(vocab_size=61, d_model=32, num_heads=4,
                         num_layers=2, d_ff=16, max_len=32,
                         pos_encoding="rope", attn_impl="xla",
                         norm="rmsnorm", qk_norm=True, num_experts=4,
                         experts_per_token=2, tie_embeddings=False,
                         seed=3).init()


def _executions(fn):
    """Device program executions ``fn()`` launches, counted in a
    ``jax.profiler`` trace of the CPU client (one ``PjRtCpuExecutable::
    Execute`` event a launch)."""
    import glob
    import tempfile

    import jax

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        planes = jax.profiler.ProfileData.from_file(path).planes
        return sum(ev.name == "PjRtCpuExecutable::Execute"
                   for plane in planes for line in plane.lines
                   for ev in line.events)


def _module_lm():
    """Two latent-attention layers, routed experts in the second, and a
    multi-token-prediction module the server drafts from (float32;
    ``tests/test_gigachat_mtp.py`` holds the model to its reference)."""
    return TransformerLM(
        vocab_size=61, d_model=32, num_heads=4, num_layers=2, d_ff=16,
        max_len=32, pos_encoding="rope", norm="rmsnorm",
        tie_embeddings=False, seed=3, num_experts=8, experts_per_token=2,
        norm_topk_prob=True, mixers=("mla",) * 2, ffns=("glu", "moe"),
        glu_width=32, mtp={"loss_weight": 0.3},
        mla={"q_lora_rank": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
             "qk_rope_head_dim": 8, "v_head_dim": 12, "gate": False}).init()


# every kind of decode block: the model, what the server is built with, and
# the tokens one dispatch can hold for a slot at most
KINDS = {
    "plain": (_routed_lm, {}, 1),
    "fused": (lambda: _lm("rope", max_len=32), {"fuse_steps": 4}, 4),
    "draft": (lambda: _lm("rope", max_len=32),
              {"draft_layers": 1, "spec_tokens": 2}, 3),
    "module": (_module_lm, {}, 2),
}


class TestPipelinedLoop:
    SLOTS, MAX_LEN, BUCKETS = 3, 32, (8, 16, 32)
    # (prompt length, max_new_tokens): ends at admission (1), after one
    # decode step (2), exactly at max_len (20 + 12), and more requests
    # than slots, so a slot is re-used the step after the host sees it free
    EARLY = [(5, 9), (11, 1), (7, 2), (20, 12), (3, 6)]
    LATE = [(9, 5), (4, 1), (6, 8)]     # submitted mid-stream

    def _server(self, lm, sampled, **kw):
        sampling = dict(temperature=0.8, top_k=7) if sampled else {}
        kw.setdefault("record_routing", bool(lm.num_experts))
        return DecodeServer(lm, slots=self.SLOTS, max_len=self.MAX_LEN,
                            buckets=self.BUCKETS, **sampling, **kw)

    def _reference(self, lm, sampled):
        sampling = dict(temperature=0.8, top_k=7) if sampled else {}
        return SyncLoop(lm, self.SLOTS, self.MAX_LEN,
                        buckets=self.BUCKETS, **sampling)

    @staticmethod
    def _same_routing(req, want):
        got = [np.concatenate(x, axis=1) for x in zip(*req.routing)]
        ref = [np.concatenate(x, axis=1) for x in zip(*want.routing)]
        assert got[0].shape[1] == len(req.prompt) + len(req.tokens) - 1
        for g, w in zip(got, ref):
            assert np.array_equal(g, w)

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize("model", ["dense", "routed"])
    def test_equals_the_synchronous_loop(self, rng, model, sampled):
        """Token for token, key for key and routing row for routing row
        what the synchronous loop gives the same requests in the same
        admission order, with admissions mid-stream."""
        lm = _lm("rope", max_len=32) if model == "dense" else _routed_lm()
        work = [(p, m, seed) for seed, (p, m) in enumerate(
            zip(_prompts(rng, [n for n, _ in self.EARLY + self.LATE]),
                [m for _, m in self.EARLY + self.LATE]))]
        ref = self._reference(lm, sampled)
        want = [ref.submit(p, m, seed) for p, m, seed in work]
        ref.drain()

        srv = self._server(lm, sampled)
        reqs = [srv.submit(p, m, seed=seed)
                for p, m, seed in work[:len(self.EARLY)]]
        for _ in range(4):
            srv.step()
        # mid-stream, with a block unread: the device's keys are those of
        # every DISPATCHED token, one ahead of the tokens the host holds
        assert srv._unread is not None
        for slot, req in srv._owed().items():
            _, _, key = srv.engine.slot_state(slot)
            assert np.array_equal(key, want[reqs.index(req)].keys[
                len(req.tokens)])
        reqs += [srv.submit(p, m, seed=seed)
                 for p, m, seed in work[len(self.EARLY):]]
        srv.drain()
        assert srv._unread is None and not srv.busy()
        assert len({r.slot for r in reqs}) < len(reqs)     # slots re-used
        for req, ref_req in zip(reqs, want):
            assert req.state == "finished"
            assert req.tokens == ref_req.tokens
            if lm.num_experts:
                self._same_routing(req, ref_req)
        # a slot's key froze with its last request's last token
        last = {r.slot: i for i, r in enumerate(reqs)}
        for slot, i in last.items():
            cursor, tok, key = srv.engine.slot_state(slot)
            assert np.array_equal(key, want[i].keys[-1])
            assert tok == reqs[i].tokens[-1]
            assert cursor == len(reqs[i].prompt) + len(reqs[i].tokens) - 1
        assert srv.steps == srv.stats()["decode_dispatches"]

    @pytest.mark.parametrize("model", ["dense", "routed"])
    def test_deadline_and_cancel_with_a_block_in_flight(self, rng, model):
        """A request shed on its deadline, and one canceled, while a block
        holding their next token is unread: the token is dropped, the
        slots stop decoding and are re-used, the others never notice."""
        lm = _lm("rope", max_len=32) if model == "dense" else _routed_lm()
        prompts = _prompts(rng, (5, 9, 7, 6, 4))
        ref = self._reference(lm, False)
        want = [ref.submit(p, 8, s) for s, p in enumerate(prompts)]
        ref.drain()

        clock = ManualClock()
        srv = self._server(lm, False, clock=clock)
        keep = srv.submit(prompts[0], 8, seed=0)
        late = srv.submit(prompts[1], 8, seed=1, deadline_s=5.0)
        loser = srv.submit(prompts[2], 8, seed=2)
        srv.step()
        srv.step()
        assert set(srv._unread[2]) == {0, 1, 2}
        held = [len(r.tokens) for r in (late, loser)]
        clock.t = 10.0
        loser.canceled = True
        more = [srv.submit(p, 8, seed=s)
                for s, p in enumerate(prompts[3:], start=3)]
        srv.drain()
        assert late.state == "shed" and loser.state == "canceled"
        assert [len(late.tokens), len(loser.tokens)] == held
        assert late.tokens == want[1].tokens[:held[0]]
        assert srv.expired_in_flight == 1
        assert {r.slot for r in more} == {1, 2}
        for req, ref_req in zip([keep] + more, [want[0]] + want[3:]):
            assert req.state == "finished"
            assert req.tokens == ref_req.tokens
            if lm.num_experts:
                self._same_routing(req, ref_req)

    @staticmethod
    def _run(srv, submit, sync):
        """Step ``srv`` dry, the requests of ``submit[i]`` entering before
        step i; ``sync``: with a ``flush()`` after every step, which is the
        synchronous order (a block is read in the step that dispatched it).
        Returns the requests and the ``serve.decode`` spans' attrs."""
        tr = SpanTracer()
        set_tracer(tr)
        try:
            reqs, i = [], 0
            while srv.busy() or i < len(submit):
                if i < len(submit):
                    reqs += [srv.submit(p, m, seed=seed)
                             for p, m, seed in submit[i]]
                srv.step()
                if sync:
                    srv.flush()
                i += 1
        finally:
            set_tracer(None)
        return reqs, [sp.attrs for sp in tr.spans()
                      if sp.name == "serve.decode"]

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize("kind", list(KINDS))
    def test_every_kind_is_read_one_dispatch_behind(self, rng, kind,
                                                    sampled):
        """A plain step, K fused steps, rounds of a separate draft and
        rounds of the model's own module: dispatched while the block before
        is unread, a server gives every request the tokens, the drafts and
        the routing rows of the synchronous order, with admissions
        mid-stream, slots re-used and requests that end inside a block."""
        make, kw, most = KINDS[kind]
        lm = make()
        # the rounds verify up to ``spec_tokens`` positions past the end
        slack = kw.get("spec_tokens", 1 if kind == "module" else 0)
        work = [(p, min(m, self.MAX_LEN - len(p) - slack), seed)
                for seed, (p, m) in enumerate(zip(
                    _prompts(rng, [n for n, _ in self.EARLY + self.LATE]),
                    [m for _, m in self.EARLY + self.LATE]))]
        submit = [work[:len(self.EARLY)], [], [], [], work[len(self.EARLY):]]
        want, sync = self._run(self._server(lm, sampled, **kw), submit, True)
        srv = self._server(lm, sampled, **kw)
        reqs, spans = self._run(srv, submit, False)
        assert not any(sp["ahead"] for sp in sync) and sum(
            sp["ahead"] for sp in spans) >= len(spans) - 4
        assert len({r.slot for r in reqs}) < len(reqs)     # slots re-used
        for req, ref_req in zip(reqs, want):
            assert req.state == "finished"
            assert len(req.tokens) == req.max_new_tokens
            assert req.tokens == ref_req.tokens
            assert req.drafts == ref_req.drafts
            if lm.num_experts:
                self._same_routing(req, ref_req)
        assert (kind == "module") == (reqs[0].drafts is not None)
        # the host's cursors are the device's once every block is read,
        # and the slots a dispatch served are no fewer than the rows that
        # yielded a token
        assert np.array_equal(srv._cursors,
                              np.asarray(srv.engine.cache.loop["cursors"]))
        st = srv.stats()
        assert st["decode_tokens"] == sum(m - 1 for _, m, _ in work)
        assert st["decode_tokens"] <= most * srv.slot_dispatches
        if not srv.engine.spec:
            assert st["empty_dispatches"] == 0

    @pytest.mark.parametrize("kind", list(KINDS))
    def test_ahead_of_a_lone_request(self, rng, kind):
        """``ahead`` reads 0, 1, 1, ..., 0 on every kind: the first
        dispatch had no block before it, and the last span only reads."""
        make, kw, most = KINDS[kind]
        srv = self._server(make(), False, **kw)
        _, spans = self._run(srv, [[(_prompts(rng, (5,))[0], 21, 0)]], False)
        n = srv.stats()["decode_dispatches"]
        assert n >= -(-20 // most) and len(spans) == n + 1
        assert [sp["ahead"] for sp in spans] == [0] + [1] * (n - 1) + [0]
        assert [sp["live"] for sp in spans] == [1] * n + [0]
        assert [sp["kind"] for sp in spans] == [srv._decode_kind] * (n + 1)
        assert srv.stats()["decode_ahead_share"] == round((n - 1) / n, 4)

    @pytest.mark.parametrize("kind", [k for k in KINDS if k != "plain"])
    def test_sweep_and_cancel_with_fused_steps_or_rounds_unread(self, rng,
                                                                 kind):
        """``test_deadline_and_cancel_with_a_block_in_flight`` for the
        blocks that hold more than a token a slot: the rows of a request
        shed on its deadline and of one canceled, in a block that is
        unread, are dropped; the requests admitted into their slots before
        that block is read take none of its tokens."""
        make, kw, most = KINDS[kind]
        lm = make()
        prompts = _prompts(rng, (5, 9, 7, 6, 4))
        ref = self._server(lm, False, **kw)
        want = [ref.submit(p, 14, seed=s) for s, p in enumerate(prompts)]
        ref.drain()

        clock = ManualClock()
        srv = self._server(lm, False, clock=clock, **kw)
        keep = srv.submit(prompts[0], 14, seed=0)
        late = srv.submit(prompts[1], 14, seed=1, deadline_s=5.0)
        loser = srv.submit(prompts[2], 14, seed=2)
        # the module's prompts enter a block a step, one after another
        while not (srv._unread and set(srv._unread[2]) == {0, 1, 2}):
            srv.step()
        srv.step()
        assert set(srv._unread[2]) == {0, 1, 2}
        held = [len(r.tokens) for r in (late, loser)]
        assert all(1 < n < 14 for n in held)
        clock.t = 10.0
        loser.canceled = True
        more = [srv.submit(p, 14, seed=s)
                for s, p in enumerate(prompts[3:], start=3)]
        srv.step()
        # swept and re-admitted in one step, the old tenants' block unread
        # until the end of it (the module's second prompt a step later)
        assert srv._slot_req[1] is more[0]
        srv.drain()
        assert late.state == "shed" and loser.state == "canceled"
        assert [len(late.tokens), len(loser.tokens)] == held
        assert late.tokens == want[1].tokens[:held[0]]
        assert loser.tokens == want[2].tokens[:held[1]]
        assert srv.expired_in_flight == 1
        assert {r.slot for r in more} == {1, 2}
        for req, ref_req in zip([keep] + more, [want[0]] + want[3:]):
            assert req.state == "finished"
            assert req.tokens == ref_req.tokens
            assert req.drafts == ref_req.drafts
        for req in [keep] + more:    # the swept slots' new tenants too
            assert srv.engine.slot_state(req.slot)[0] == srv._cursors[
                req.slot] == len(req.prompt) + len(req.tokens) - 1

    @pytest.mark.parametrize("kind", list(KINDS))
    def test_a_slot_is_free_once_its_last_block_is_dispatched(self, rng,
                                                              kind):
        """Reading a block one dispatch behind costs a request's slot
        nothing: the slot is free when the block that is certain to end
        its request is dispatched, so the next tenant enters at the step
        it enters in the synchronous order, while that block is unread;
        the request that left books its last tokens, its TPOT from its
        own clock, and retires one read later."""
        make, kw, most = KINDS[kind]
        lm = make()
        pa, pb = _prompts(rng, (5, 7))
        tpot = metrics().histogram("serve_tpot_seconds")

        def run(sync):
            clock = ManualClock()
            srv = DecodeServer(lm, slots=1, max_len=self.MAX_LEN,
                               buckets=self.BUCKETS, clock=clock, **kw)
            a = srv.submit(pa, 1 + 2 * most, seed=0)
            b = srv.submit(pb, 4, seed=1)
            while srv.busy():
                clock.t += 1.0
                srv.step()
                if sync:
                    srv.flush()
                if a.state == "running" and srv._slot_req[0] is None:
                    assert 0 in srv._unread[3] and srv.free_slot_count()
                    assert len(a.tokens) < a.max_new_tokens
            # the slot's cursor is its last tenant's (a draft accepted
            # past the end moves it further than the tokens taken)
            assert int(srv._cursors[0]) == srv.engine.slot_state(0)[0] \
                >= len(pb) + len(b.tokens) - 1
            return a, b

        spent = tpot.value()["sum"]
        (a0, b0), (a, b) = run(True), run(False)
        spent = tpot.value()["sum"] - spent
        # the next tenant enters in the step that reads the last block of
        # the one before it: the step after it retired, read synchronously
        assert b0.first_token_s == a0.finish_s + 1.0
        assert b.first_token_s == a.finish_s
        for req, want in ((a, a0), (b, b0)):
            assert req.state == "finished" and req.tokens == want.tokens
            assert req.drafts == want.drafts
            assert req.first_token_s == want.first_token_s
            assert req.finish_s == want.finish_s + 1.0
        # an observation a token, summing to each request's decode span
        assert spent == pytest.approx(sum(
            r.finish_s - r.first_token_s for r in (a0, b0, a, b)))

    def test_a_swept_slot_stops_decoding_on_the_device(self, rng):
        """The lone request is canceled with its next token unread:
        ``busy()`` holds until that block is read (and dropped), and the
        device owes the slot nothing more."""
        srv = self._server(_lm("rope", max_len=32), False)
        req = srv.submit(_prompts(rng, (5,))[0], 8)
        srv.step()
        req.canceled = True
        assert srv.step() and srv._unread is None       # swept, then read
        assert req.state == "canceled" and len(req.tokens) == 1
        assert not srv.busy() and not srv.step()
        assert int(np.asarray(srv.engine.cache.loop["remaining"]).sum()) == 0
        assert srv.steps == 1

    def test_flush_points(self, rng):
        """``stats()``, ``drain()`` and ``flush()`` read the unread block
        first; ``busy()`` is true while there is one."""
        srv = self._server(_lm("rope", max_len=32), False)
        req = srv.submit(_prompts(rng, (5,))[0], 6)
        srv.step()
        srv.step()
        assert srv._unread is not None and srv.busy()
        assert (srv.steps, len(req.tokens)) == (1, 2)
        assert srv.stats()["steps"] == 2 and len(req.tokens) == 3
        assert srv._unread is None
        srv.step()
        srv.flush()
        assert srv._unread is None and len(req.tokens) == 4
        srv.flush()                                     # nothing to read
        assert srv.steps == 3
        assert srv.drain(max_steps=1) == 1
        assert srv._unread is None and len(req.tokens) == 5
        srv.drain()
        assert req.state == "finished" and len(req.tokens) == 6
        assert srv.steps == 5

    @pytest.mark.parametrize("kind", ["plain", "module"])
    def test_steady_step_sends_nothing_and_launches_one_program(self, rng,
                                                                kind):
        """Between two decode steps — or two rounds drafted from the
        model's own module — with no admission nothing travels host ->
        device — explicit puts (``jnp.asarray``) included, which is what
        the loop made before its state lived on the device — and exactly
        one device program is launched."""
        import jax

        srv = self._server(_lm("rope", max_len=32) if kind == "plain"
                           else _module_lm(), False)
        srv.submit(_prompts(rng, (5,))[0], 20)
        for _ in range(3):
            srv.step()
        probe = (srv.engine.decode if kind == "plain"
                 else lambda: srv.engine.decode_spec(1))
        if _executions(probe) != 1:
            pytest.skip("this jax's CPU trace does not show launches")
        srv.step()          # books the extra block's token too
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            assert _executions(srv.step) == 1
            srv.step()

    def test_decode_ahead_share(self, rng):
        """0 for a lone 2-token request (its one dispatch had no block
        before it), towards 1 in a long run; the counter and the span
        attribute say the same."""
        lm = _lm("rope", max_len=64)
        ahead0 = metrics().counter("serve_decode_ahead_total").value()
        srv = DecodeServer(lm, slots=2, max_len=64)
        srv.submit(_prompts(rng, (5,))[0], 2)
        srv.drain()
        assert srv.stats()["decode_ahead_share"] == 0.0
        assert srv.stats()["decode_dispatches"] == 1
        tr = SpanTracer()
        set_tracer(tr)
        try:
            srv = DecodeServer(lm, slots=2, max_len=64)
            srv.submit(_prompts(rng, (5,))[0], 41)
            srv.drain()
        finally:
            set_tracer(None)
        assert srv.stats()["decode_ahead_share"] == round(39 / 40, 4)
        assert metrics().counter(
            "serve_decode_ahead_total").value() == ahead0 + 39
        decode = [sp for sp in tr.spans() if sp.name == "serve.decode"]
        # 40 dispatches, the first not ahead; one more span reads the last
        assert [sp.attrs["ahead"] for sp in decode] == [0] + [1] * 39 + [0]
        assert [sp.attrs["live"] for sp in decode] == [1] * 40 + [0]
        # the fused path is read one dispatch behind too: two dispatches
        # of four steps for eight tokens, the second ahead
        fused = DecodeServer(lm, slots=2, max_len=64, fuse_steps=4)
        fused.submit(_prompts(rng, (5,))[0], 9)
        fused.drain()
        assert fused.stats()["decode_dispatches"] == 2
        assert fused.stats()["decode_ahead_share"] == 0.5


class TestKernelRead:
    """The pool read by the Pallas kernel (interpreted here) under a
    server: its work list is the live slots' own key blocks."""

    SLOTS, MAX_LEN, BUCKETS, BLOCK = 2, 96, (16, 32, 64, 96), 16

    def _server(self, monkeypatch, kernel, **kw):
        """A server over 128-wide heads whose decode programs read the
        pool by the kernel (``kernel``) or by the XLA op, with key blocks
        of 16 positions so that a 96-position slot has six."""
        from deeplearning4j_tpu.pallas import decode_attention
        from deeplearning4j_tpu.serving import engine as eng

        monkeypatch.setattr(decode_attention, "_BLOCK_BYTES",
                            self.BLOCK * 128 * 4)
        lm = _lm("rope", d_model=256, num_heads=2, num_kv_heads=1)
        srv = DecodeServer(lm, slots=self.SLOTS, max_len=self.MAX_LEN,
                           buckets=self.BUCKETS, **kw)
        # the decode programs are built at the first dispatch
        srv.engine._decode_jit = lambda donate, impl, *bound: (
            eng.DecodeEngine._decode_jit(
                srv.engine, donate,
                functools.partial(impl, pool_kernel=kernel), *bound))
        return srv

    def _serve(self, rng_seed, srv, poison=False):
        """A long request and a short one; when the long one has left, a
        shorter one takes its slot. ``poison``: before that, the slot's
        key blocks past the newcomer's reach are overwritten with NaN —
        a read of the previous tenant's keys would show in the tokens."""
        import jax.numpy as jnp

        rng = np.random.default_rng(rng_seed)
        long_, stay, short = _prompts(rng, (70, 20, 10))
        a = srv.submit(long_, 4)
        b = srv.submit(stay, 40)
        while a.state != "finished":
            srv.step()
        srv.flush()
        if poison:
            pool = srv.engine.cache
            reach = 2 * self.BLOCK      # prompt 10 + 6 tokens: blocks 0, 1
            pool.k = pool.k.at[:, a.slot, reach:].set(jnp.nan)
            pool.v = pool.v.at[:, a.slot, reach:].set(jnp.nan)
        c = srv.submit(short, 6)
        srv.drain()
        assert c.slot == a.slot and b.state == c.state == "finished"
        return [list(r.tokens) for r in (a, b, c)]

    @pytest.mark.parametrize("kind", ["plain", "fused"])
    def test_tokens_before_and_after_a_slot_changes_tenant(
            self, monkeypatch, kind):
        kw = {"fuse_steps": 3} if kind == "fused" else {}
        want = self._serve(5, self._server(monkeypatch, False, **kw))
        srv = self._server(monkeypatch, True, **kw)
        assert self._serve(5, srv, poison=True) == want
        # the host's cursors are the device's, and what the kernel read
        # is less than every slot's span: the long tenant's frozen cursor
        # counted in the pool's while its slot was free
        assert np.array_equal(srv._cursors,
                              np.asarray(srv.engine.cache.loop["cursors"]))
        st = srv.stats()
        assert 0 < st["kv_blocks"] < st["kv_blocks_pool"]
        assert st["kv_blocks_share"] == round(
            st["kv_blocks"] / st["kv_blocks_pool"], 4)

    def test_kv_blocks_on_the_span_and_the_counters(self, monkeypatch):
        reg = metrics()
        read0 = reg.counter("serve_decode_kv_blocks_total").value()
        pool0 = reg.counter("serve_decode_kv_blocks_pool_total").value()
        tr = SpanTracer()
        set_tracer(tr)
        try:
            srv = self._server(monkeypatch, False)
            self._serve(7, srv)
        finally:
            set_tracer(None)
        decode = [sp.attrs for sp in tr.spans()
                  if sp.name == "serve.decode" and sp.attrs["live"]]
        assert all(0 < a["kv_blocks"] <= a["kv_blocks_pool"] for a in decode)
        assert sum(a["kv_blocks"] for a in decode) == srv.kv_blocks == (
            reg.counter("serve_decode_kv_blocks_total").value() - read0)
        assert sum(a["kv_blocks_pool"] for a in decode) == (
            srv.kv_blocks_pool) == reg.counter(
                "serve_decode_kv_blocks_pool_total").value() - pool0
        # the first dispatch: prompts 70 and 20 -> cursors 70 and 20 ->
        # blocks 0..4 and 0..1
        assert (decode[0]["kv_blocks"], decode[0]["kv_blocks_pool"]) == (7, 7)
        # a pool without the kernel's blocks (32-wide heads) counts none
        plain = DecodeServer(_lm("rope"), slots=2, max_len=64)
        plain.submit(np.arange(1, 6, dtype=np.int32), 3)
        plain.drain()
        assert plain.stats()["kv_blocks_share"] is None
