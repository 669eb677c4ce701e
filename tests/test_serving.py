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
from deeplearning4j_tpu.serving import kv_cache
from deeplearning4j_tpu.serving import (
    DecodeServer, ServeQueueFull, SlotKVCache, kv_pool_nbytes,
    max_slots_in_budget, poisson_schedule, run_open_loop,
    serve_max_queue, serve_slots)

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
# one decode block a model: the plain step's program, and the names that left
# ---------------------------------------------------------------------------
class TestOneDecodeBlock:
    def test_fuse_steps_one_is_pr10_bitwise(self, rng):
        """The server runs the PR-10 single-step program — the ("decode",
        S) cache key, one dispatch a token, ``generate``'s tokens — and no
        other decode program; ``fuse_steps=1`` (what the benchmark's
        drivers pass) builds the same server and any other value
        raises."""
        lm = _lm()
        prompts = _prompts(rng, (5, 11))
        refs = [np.asarray(lm.generate(p[None], m))[0]
                for p, m in zip(prompts, (6, 4))]
        srv = DecodeServer(lm, slots=2, max_len=96, fuse_steps=1)
        reqs = [srv.submit(p, m) for p, m in zip(prompts, (6, 4))]
        srv.drain()
        assert [s for s in srv.engine._programs if s[0].startswith(
            "decode")] == [("decode", 2)]
        assert srv.steps == 5   # max(6,4)-1: one dispatch per token
        for req, ref in zip(reqs, refs):
            assert np.array_equal(req.output, ref)
        st = srv.stats()
        assert st["tokens_per_slot_dispatch"] == 1.0
        assert "fuse_steps" not in st and not hasattr(srv, "fuse_steps")
        with pytest.raises(ValueError, match="one decode step"):
            DecodeServer(lm, slots=2, max_len=96, fuse_steps=2)

    @pytest.mark.parametrize("kw,error,variable", [
        ({"fuse_steps": 2}, ValueError, "DL4J_SERVE_FUSE_STEPS"),
        ({"kv_dtype": "int8"}, ValueError, "DL4J_SERVE_KV_DTYPE"),
        ({"draft_layers": 1}, TypeError, "DL4J_SERVE_DRAFT_LAYERS"),
    ], ids=["fuse_steps", "int8", "draft_layers"])
    def test_the_removed_names_refuse(self, monkeypatch, kw, error,
                                      variable):
        """K fused steps, the int8 pool and a separate draft are not
        options of the server or the engine: asking for one fails at
        construction, and the variable that used to ask for it is read by
        nobody."""
        from deeplearning4j_tpu.serving import DecodeEngine

        lm = _lm()
        with pytest.raises(error):
            DecodeServer(lm, slots=1, max_len=96, **kw)
        if "fuse_steps" not in kw:      # the engine never took it
            with pytest.raises(error):
                DecodeEngine(lm, 1, max_len=96, **kw)
        monkeypatch.setenv(variable, str(list(kw.values())[0]))
        srv = DecodeServer(lm, slots=1, max_len=96)
        assert (srv._decode_kind, srv.engine.spec, srv.engine.kv_dtype) == (
            "plain", False, "float32")


# ---------------------------------------------------------------------------
# the pool's store dtype
# ---------------------------------------------------------------------------
class TestPoolDtype:
    def test_validate_cache_budget_prices_the_pool(self):
        """PR 8's budget validator sees the pool the runtime actually
        allocated: predicted nbytes == measured device bytes, and the
        bfloat16 pool measures half of float32."""
        from deeplearning4j_tpu.monitor.memory import validate_cache_budget
        lm = _lm()
        out = {}
        for dt in ("float32", "bfloat16"):
            cache = SlotKVCache(lm, slots=4, max_len=96, kv_dtype=dt)
            v = validate_cache_budget(cache)
            assert v["within_tolerance"], v
            assert v["predicted_per_shard_bytes"] \
                == v["measured_per_device_bytes"] == cache.nbytes \
                == kv_pool_nbytes(lm, 4, 96, dt)
            out[dt] = v["measured_per_device_bytes"]
        assert out["float32"] == 2 * out["bfloat16"]

    def test_max_slots_in_budget_multiplies(self):
        lm = _lm()
        budget = 64 * 1024 * 1024
        n_f32 = max_slots_in_budget(lm, 96, budget, "float32")
        n_bf16 = max_slots_in_budget(lm, 96, budget, "bfloat16")
        assert n_bf16 in (2 * n_f32, 2 * n_f32 + 1) and n_f32 > 0
        assert max_slots_in_budget(lm, 96, budget) == n_f32   # the model's
        assert max_slots_in_budget(lm, 96, 0, "bfloat16") == 0

    def test_kv_dtype_validation(self, monkeypatch):
        """Two float names and their aliases; ``"int8"`` is refused as
        ``"int4"`` is; no variable names a default: unset, the pool stays
        in the model's compute dtype."""
        lm = _lm()
        for bad in ("int4", "int8"):
            with pytest.raises(ValueError, match="kv_dtype"):
                SlotKVCache(lm, slots=1, kv_dtype=bad)
        assert SlotKVCache(lm, slots=1, kv_dtype="bf16").kv_dtype \
            == "bfloat16"
        monkeypatch.setenv("DL4J_SERVE_KV_DTYPE", "bf16")
        assert SlotKVCache(lm, slots=1).kv_dtype == "float32"
        srv = DecodeServer(lm, slots=1, max_len=96, kv_dtype="bfloat16")
        assert srv.stats()["kv_dtype"] == "bfloat16"
        assert srv.engine.cache.k.dtype == "bfloat16"


# ---------------------------------------------------------------------------
# six tiny models, one for each thing a slot can hold
# ---------------------------------------------------------------------------
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


_MLA = {"kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
        "v_head_dim": 8}


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


def _hybrid_lm():
    """A Kimi Delta Attention layer under a latent-attention layer: a slot
    holds a recurrent matrix, a convolution tail and latent rows, and its
    prompt is prefilled whole (``tests/test_ling_hybrid.py`` holds the
    model to its reference)."""
    return TransformerLM(
        vocab_size=61, d_model=32, num_heads=4, num_layers=2, d_ff=16,
        max_len=32, pos_encoding="rope", attn_impl="xla", norm="rmsnorm",
        tie_embeddings=False, seed=3, rope_interleaved=True,
        mixers=("kda", "mla"), ffns=("glu", "glu"), glu_width=32,
        kda={"head_dim": 8, "conv": 4, "lower": -5.0}, mla=_MLA).init()


def _sparse_lm():
    """Latent attention over a lightning indexer's selection of 4 keys (the
    second layer shares the first's), routed experts in the second layer: a
    slot holds latent rows and index keys, and its prompt is prefilled in
    blocks (``tests/test_glm_dsa.py`` holds the model to its reference)."""
    return TransformerLM(
        vocab_size=61, d_model=32, num_heads=4, num_layers=2, d_ff=16,
        max_len=32, pos_encoding="rope", norm="rmsnorm",
        tie_embeddings=False, seed=3, rope_interleaved=True, num_experts=4,
        experts_per_token=2, norm_topk_prob=True, mixers=("mla",) * 2,
        ffns=("glu", "moe"), glu_width=32,
        mla=dict(_MLA, q_lora_rank=16, gate=False),
        indexers=("full", "shared"),
        dsa={"n_heads": 2, "head_dim": 8, "topk": 4, "rope_dim": 4}).init()


def _gdn_lm(mixers=("gdn", "attn")):
    """A Gated DeltaNet layer under a gated softmax-attention layer of two
    kv heads 256 wide: a slot holds a recurrent state and a convolution
    tail beside a K/V pool stored as rows (``kv_cache.pool_shape``;
    ``tests/test_qwen3next.py`` holds the model to its reference)."""
    return TransformerLM(
        vocab_size=61, d_model=32, num_heads=4, num_kv_heads=2,
        num_layers=len(mixers), d_ff=16, max_len=32, pos_encoding="rope",
        attn_impl="xla", norm="rmsnorm", tie_embeddings=False, seed=3,
        mixers=mixers, ffns=("glu",) * len(mixers), glu_width=32,
        gdn={"key_heads": 2, "value_heads": 4, "head_dim": 8, "conv": 4},
        attn={"head_dim": 256, "rotary_dim": 8, "head_norm": True,
              "gate": True}).init()


# what a slot holds and how its prompt is admitted: the model, the tokens one
# dispatch can hold for a slot at most, and the positions of a prefill block
# where the test sets them (None: the engine's, one block a rung here)
KINDS = {
    "dense": (lambda: _lm("rope", max_len=32), 1, None),
    "routed": (_routed_lm, 1, None),
    "module": (_module_lm, 2, None),
    "hybrid": (_hybrid_lm, 1, None),
    "sparse": (_sparse_lm, 1, 8),
    "gdn": (_gdn_lm, 1, None),
    # a seventh: K/V under learned positions and a sliding window of 8
    "window": (lambda: _lm(max_len=32, attn_window=8), 1, None),
}


def _kind(monkeypatch, kind):
    """``KINDS[kind]``'s model and most tokens a dispatch, with the
    engine's prefill block set to the kind's."""
    from deeplearning4j_tpu.serving import engine as eng

    make, most, block = KINDS[kind]
    if block is not None:
        monkeypatch.setattr(eng, "PREFILL_BLOCK", block)
    return make(), most


# ---------------------------------------------------------------------------
# the decode-family programs update the donated pool in place (PR 24)
# ---------------------------------------------------------------------------
def _slab_step_reference(model, params, kv, toks, positions):
    """The decode-family forward as it was before PR 24, kept here as the
    reference: each layer's ``[S, T, Hkv, Dh]`` slab is sliced out of the
    pool, the new rows are scattered into the copy, attention reads it, and
    the slabs are stacked back into a fresh pool (of the shape it came in:
    five axes, or the rows of wide heads). ``toks`` and ``positions`` are
    ``[S, Q]``. Returns ``(logits [S, Q, V], pool)``."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.attention import grouped_query_attention

    cdt = model.policy.compute_dtype
    slots, hkv = toks.shape[0], model.num_kv_heads
    slab_shape = (slots, -1, hkv, kv["k"].shape[-1])
    t_max = kv["k"][0].reshape(slab_shape).shape[1]
    h = jnp.take(params["embed"], toks, axis=0)
    if model.pos_encoding == "learned":
        h = h + params["pos"][positions]
    h = model.policy.cast_compute(h)
    live = jnp.arange(t_max)[None, None, :] <= positions[:, :, None]
    if model.attn_window is not None:
        live &= (jnp.arange(t_max)[None, None, :]
                 > positions[:, :, None] - model.attn_window)
    rows = jnp.arange(slots)
    out = {name: [] for name in kv}

    def cached_attention(li):
        def attn(q, kk, vv):
            slabs = []
            for name, new in (("k", kk), ("v", vv)):
                slab = kv[name][li].reshape(slab_shape)
                slab = slab.at[rows[:, None], positions].set(
                    new.astype(slab.dtype))
                out[name].append(slab.reshape(kv[name].shape[1:]))
                slabs.append(slab.astype(cdt))
            return grouped_query_attention(q, *slabs, mask=live)
        return attn

    for li, blk in enumerate(params["blocks"]):
        h, _, _ = model._block(blk, h, attention=cached_attention(li),
                               positions=positions)
    return (model._unembed(params, h),
            {name: jnp.stack(slabs) for name, slabs in out.items()})


def _random_pool(rng, lm, slots, max_len, kv_dtype):
    """A pool of seeded content."""
    import jax.numpy as jnp

    cache = SlotKVCache(lm, slots, max_len, kv_dtype)
    return {name: jnp.asarray(rng.normal(size=arr.shape), arr.dtype)
            for name, arr in cache.state.items()}


# program x what the pool it takes holds: the model, by its kind
ALIASED = [
    ("decode", "dense"),        # K/V, five axes
    ("decode", "gdn"),          # K/V as rows, a recurrent state, a tail
    ("decode", "hybrid"),       # a recurrent matrix, a tail, latent rows
    ("decode", "sparse"),       # latent rows, index keys
    ("verify", "dense"),
    ("verify", "module"),       # latent rows, the module's among them
    ("round", "module"),
    ("prefill_block", "sparse"),
    ("prefill_block", "module"),
]


class TestInPlacePool:
    SLOTS, MAX_LEN = 3, 24

    @pytest.mark.parametrize("program,kind", ALIASED,
                             ids=[f"{p}-{k}" for p, k in ALIASED])
    def test_program_aliases_the_pool(self, program, kind):
        """Compiled with the pool donated, a decode-family program's
        output pool IS its input pool — every array of the layout, whatever
        kind (``alias_size_in_bytes``) — and its temporaries stay under
        half a pool: before PR 24 the slabs were sliced out and stacked
        back, one whole pool of temporaries."""
        import functools

        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.serving import engine as eng

        # four layers: XLA:CPU still copies one layer's K and V slabs out
        # for the attention dots, a quarter of the pool
        slots, max_len = 8, 512
        lm = (_lm("rope", max_len=max_len, num_layers=4) if kind == "dense"
              else _gdn_lm(("gdn",) + ("attn",) * 4) if kind == "gdn"
              else KINDS[kind][0]())
        cache = SlotKVCache(lm, slots, max_len, "float32")
        kv = cache.state
        assert sum(len(v) if isinstance(v, list) else 1 for v in kv.values(
            )) == sum(map(len, kv_cache.pool_layout(
                lm, slots, max_len, "float32").values()))
        vec = jnp.zeros(slots, jnp.int32)
        keys = jnp.zeros((slots, 2), jnp.uint32)
        sampler = eng._row_sampler(0.0, None)
        donate = (1,)
        if program == "decode":
            fn = functools.partial(eng._serve_decode_impl, lm, sampler)
            args = (lm.params, kv, vec, vec, keys, vec > 0)
        elif program == "verify":
            fn = functools.partial(eng._serve_verify_impl, lm)
            pos = jnp.zeros((slots, 3), jnp.int32)
            args = (lm.params, kv, pos, pos)
        elif program == "round":
            fn = functools.partial(eng._serve_mtp_impl, lm, None, True, 1)
            args = (lm.params, kv, cache.loop)
        else:
            rung = 64
            fn = functools.partial(eng._serve_prefill_block_impl, lm,
                                   sampler)
            carry = {name: jnp.full(shape, fill, jnp.dtype(dt))
                     for name, (shape, dt, fill) in
                     eng.prefill_carry_layout(lm, rung).items()}
            args = (lm.params, kv, carry, jnp.zeros((1, rung), jnp.int32),
                    jnp.int32(40), jnp.int32(1), keys[0], jnp.int32(0))
            donate = (1, 2)
        mem = jax.jit(fn, donate_argnums=donate).lower(
            *args).compile().memory_analysis()
        pool = cache.nbytes
        donated = sum(int(leaf.nbytes) for i in donate
                      for leaf in jax.tree_util.tree_leaves(args[i]))
        assert mem.alias_size_in_bytes == donated >= pool
        assert mem.temp_size_in_bytes < pool // 2, (
            mem.temp_size_in_bytes, pool)

    @pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("layout", ["axes", "rows"])
    @pytest.mark.parametrize("queries", [1, 3])
    def test_bitwise_equal_to_the_slab_formulation(self, rng, layout,
                                                   kv_dtype, queries):
        """Scattering into the pool and attending to ``pool[li]`` is the
        old slice / scatter / stack step bit for bit: logits and the
        returned pool, one query a slot (decode) and several (verify), the
        pool in its five axes or stored as the rows of heads 256 wide.
        Slot 1 is frozen past ``T_max``: its write is dropped and its rows
        come back untouched."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.serving import engine as eng

        lm = (_lm("rope", attn_window=16) if layout == "axes"
              else _gdn_lm(("attn", "attn")))
        kv = _random_pool(rng, lm, self.SLOTS, self.MAX_LEN, kv_dtype)
        assert kv["k"].ndim == (5 if layout == "axes" else 4)
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
            assert np.array_equal(
                got[:, 1], np.asarray(kv[name].astype(jnp.float32))[:, 1])
            assert not np.array_equal(
                got[:, 0], np.asarray(kv[name].astype(jnp.float32))[:, 0])


# ---------------------------------------------------------------------------
# a round's accept / resample rule, held to its law
# ---------------------------------------------------------------------------
class TestAcceptRound:
    """``engine._accept_round`` on hand-made logits: one caller is left
    (the model's own module, one proposal a round), and the rule stays
    general in the number of proposals."""

    V = 5

    @staticmethod
    def _accept(logits, d, q, act, greedy, seed=0):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.serving import engine as eng

        slots, gamma = d.shape
        keys = jax.vmap(jax.random.PRNGKey)(seed + jnp.arange(slots))
        count, corr, block, new_keys = eng._accept_round(
            jnp.asarray(act), jnp.asarray(logits, jnp.float32),
            jnp.asarray(d, jnp.int32), None if q is None else jnp.asarray(
                q, jnp.float32), keys, gamma, greedy,
            eng._filtered_logits_fn(1.0, None))
        return (np.asarray(count), np.asarray(corr), np.asarray(block),
                np.asarray(keys), np.asarray(new_keys))

    def _peaked(self, tokens):
        """Logits ``[len(tokens), V]`` whose argmax at row i is tokens[i]."""
        return 5.0 * np.eye(self.V, dtype=np.float32)[list(tokens)]

    def test_greedy_agreement_takes_every_proposal_and_the_bonus(self):
        """The target's argmax equals every proposal: a round emits them
        all and then the target's own next token, ``[count, e_1..e_G+1]``."""
        logits = np.stack([self._peaked([1, 2, 3]), self._peaked([4, 0, 2])])
        count, corr, block, keys, new_keys = self._accept(
            logits, np.array([[1, 2], [4, 0]]), None, [True, True], True)
        assert count.tolist() == [3, 3] and corr.tolist() == [3, 2]
        assert block.tolist() == [[3, 1, 2, 3], [3, 4, 0, 2]]
        assert np.array_equal(keys, new_keys)      # greedy draws nothing

    def test_greedy_disagreement_cuts_at_the_first_miss(self):
        """A proposal the target's argmax differs from ends the accepted
        prefix: the target's token takes its place, what the draft proposed
        after it is dropped even where it agrees again, and the block's
        rows past ``count`` are zeros."""
        logits = np.stack([self._peaked([1, 2, 3]), self._peaked([1, 2, 3]),
                           self._peaked([1, 2, 3])])
        d = np.array([[4, 2], [1, 4], [1, 2]])
        count, corr, block, _, _ = self._accept(
            logits, d, None, [True] * 3, True)
        assert count.tolist() == [1, 2, 3] and corr.tolist() == [1, 2, 3]
        assert block.tolist() == [[1, 1, 0, 0], [2, 1, 2, 0], [3, 1, 2, 3]]

    @pytest.mark.parametrize("gamma", [1, 2])
    def test_sampled_rule_draws_from_the_target(self, gamma):
        """Proposals drawn from ``q`` are accepted with probability
        ``min(1, p / q)``; after the first rejection the token comes from
        ``norm(max(p - q, 0))``; so every emitted token is distributed as
        the target's ``p`` at its position, whatever ``q`` was. 40,000
        slots share the distributions and differ in their keys."""
        n, v = 40_000, self.V
        rng = np.random.default_rng(gamma)
        p = rng.dirichlet(np.ones(v), gamma + 1).astype(np.float32)
        q = rng.dirichlet(np.ones(v), gamma).astype(np.float32)
        q[0, 0] = 0.0               # a token the draft never proposes
        q /= q.sum(-1, keepdims=True)
        d = np.stack([rng.choice(v, n, p=q[i]) for i in range(gamma)], 1)
        count, corr, block, keys, new_keys = self._accept(
            np.broadcast_to(np.log(p), (n, gamma + 1, v)), d,
            np.broadcast_to(q, (n, gamma, v)), np.ones(n, bool), False)
        assert not np.array_equal(keys, new_keys)
        accepted = count - 1
        tol = 4 / np.sqrt(n)        # four standard errors of a share
        # the first proposal's acceptance, given what was proposed
        for x in range(v):
            rate = (accepted[d[:, 0] == x] >= 1).mean() if q[0, x] else 0.0
            want = min(1.0, p[0, x] / q[0, x]) if q[0, x] else 0.0
            assert abs(rate - want) < 4 * tol, (x, rate, want)
        # the token after a first-position rejection is the residual's
        res = np.maximum(p[0] - q[0], 0.0)
        got = np.bincount(corr[accepted == 0], minlength=v) / max(
            1, (accepted == 0).sum())
        assert np.abs(got - res / res.sum()).max() < 4 * tol
        # and every position's emitted token is the target's p there
        for i in range(gamma + 1):
            took = count > i
            got = np.bincount(block[took, 1 + i], minlength=v) / took.sum()
            assert np.abs(got - p[i]).max() < 4 * tol, (i, got, p[i])
        assert np.array_equal(block[:, 0], count)
        assert np.array_equal(corr, block[np.arange(n), count])

    @pytest.mark.parametrize("greedy", [True, False],
                             ids=["greedy", "sampled"])
    def test_a_frozen_slot_emits_nothing(self, greedy):
        """A slot that owes no token (``act`` false) counts 0 whatever its
        row would have accepted: the host takes none of its block, and the
        slot beside it is not disturbed."""
        logits = np.stack([self._peaked([1, 2])] * 2)
        d = np.array([[1], [1]])
        q = None if greedy else np.eye(self.V, dtype=np.float32)[d]
        count, _, block, _, _ = self._accept(logits, d, q, [False, True],
                                             greedy)
        assert count.tolist() == [0, 2] and block[:, 0].tolist() == [0, 2]
        assert block[1].tolist() == [2, 1, 2]


# ---------------------------------------------------------------------------
# the plain decode loop: state on the device, read one step behind
# ---------------------------------------------------------------------------
class SyncLoop:
    """The straightforward loop the pipelined server is held to: FIFO
    admission into free slots (the whole prompt at once: every block of a
    prompt that is prefilled in blocks, back to back), then ONE decode
    dispatch per step from HOST arrays (last tokens, cursors, keys, the live
    mask; for a model that drafts from its own module also the drafts and
    the tokens owed) through the one-step program body — or the one-round
    body — one blocking read, the cursors advanced by the host — nothing
    but the pool kept on the device. Every request keeps its tokens, its
    routing rows, its selections, the drafts its rounds verified and the key
    its stream held after each token."""

    def __init__(self, lm, slots, max_len, **engine_kw):
        import functools
        from collections import deque

        import jax
        from deeplearning4j_tpu.serving import engine as eng

        self.lm, self.slots = lm, slots
        self.engine = eng.DecodeEngine(lm, slots, max_len=max_len,
                                       **engine_kw)
        if lm.mtp:
            greedy = self.engine.temperature == 0.0
            self.run = jax.jit(functools.partial(
                eng._serve_mtp_impl, lm,
                None if greedy else eng._filtered_logits_fn(
                    self.engine.temperature, self.engine.top_k), greedy, 1))
        else:
            self.run = jax.jit(functools.partial(
                eng._serve_decode_impl, lm, self.engine._sample_row))
        self.tok = np.zeros(slots, np.int32)
        self.cursors = np.zeros(slots, np.int32)
        self.keys = np.zeros((slots, 2), np.uint32)
        self.draft = np.zeros(slots, np.int32)
        self.req = [None] * slots
        self.queue = deque()

    def submit(self, prompt, max_new, seed=0):
        from types import SimpleNamespace

        req = SimpleNamespace(prompt=prompt, max_new=max_new, seed=seed,
                              tokens=[], keys=[], routing=[], selection=[],
                              drafts=[] if self.lm.mtp else None)
        self.queue.append(req)
        return req

    def _rows(self, packed):
        from deeplearning4j_tpu.serving.engine import unpack_routing

        return unpack_routing(np.asarray(packed), self.lm.experts_held,
                              self.lm.experts_per_token)[1:3]

    def _took(self, slot, req, toks, key, rows):
        req.tokens += [int(t) for t in toks]
        req.keys += [np.asarray(key)] * len(toks)
        if rows is not None:
            req.routing.append(rows)
        self.tok[slot], self.keys[slot] = toks[-1], key
        if len(req.tokens) >= req.max_new:
            self.req[slot] = None

    def _admit(self, slot, req):
        import jax

        tok, key, record = self.engine.prefill(
            req.prompt, slot, jax.random.PRNGKey(req.seed))
        routing, selection = (record if isinstance(record, tuple)
                              else (record, None))
        n = len(req.prompt)
        self.cursors[slot], self.req[slot] = n, req
        if self.lm.mtp:
            self.draft[slot] = self.engine._first_draft.pop(slot)
        if selection is not None:
            req.selection.append(np.asarray(selection)[:, None])
        self._took(slot, req, [tok], key, None if routing is None else
                   tuple(a[:, :n] for a in self._rows(routing)))

    def step(self):
        import jax.numpy as jnp

        for slot in range(self.slots):
            if self.req[slot] is None and self.queue:
                self._admit(slot, self.queue.popleft())
        live = np.array([r is not None for r in self.req])
        if not live.any():
            return bool(self.queue)
        state = self.engine.cache.state
        if self.lm.mtp:
            return self._round(state, live)
        toks, keys, state, *extra = self.run(
            self.lm.params, state, jnp.asarray(self.tok),
            jnp.asarray(self.cursors), jnp.asarray(self.keys),
            jnp.asarray(live))
        self.engine.cache.install(state)
        toks, keys = np.asarray(toks), np.asarray(keys)
        rows = self._rows(extra[0]) if self.lm.num_experts else None
        selection = np.asarray(extra[1]) if self.lm.dsa else None
        for slot in np.flatnonzero(live):
            if selection is not None:
                self.req[slot].selection.append(selection[:, slot:slot + 1])
            self._took(slot, self.req[slot], toks[slot:slot + 1], keys[slot],
                       None if rows is None else
                       tuple(a[:, slot:slot + 1] for a in rows))
            self.cursors[slot] += 1
        return True

    def _round(self, state, live):
        """One round for the live slots: what ``step`` does for a model
        that drafts from its own module."""
        import jax.numpy as jnp

        owed = np.array([0 if r is None else r.max_new - len(r.tokens)
                         for r in self.req], np.int32)
        blocks, loop, state, routing = self.run(self.lm.params, state, {
            "cursors": jnp.asarray(self.cursors), "tok": jnp.asarray(self.tok),
            "remaining": jnp.asarray(owed), "keys": jnp.asarray(self.keys),
            "draft": jnp.asarray(self.draft)})
        self.engine.cache.install(state)
        block, keys = np.asarray(blocks)[0], np.asarray(loop["keys"])
        rows = self._rows(np.asarray(routing)[0])
        self.draft = np.array(loop["draft"])
        for slot in np.flatnonzero(live):
            req, count = self.req[slot], int(block[slot, 0])
            take = min(count, int(owed[slot]))
            req.drafts.append((len(req.prompt) + len(req.tokens),
                               int(block[slot, 3])))
            self.cursors[slot] += count
            self._took(slot, req, block[slot, 1:1 + take], keys[slot], tuple(
                a.reshape(a.shape[0], self.slots, 2, -1)[:, slot, :take]
                for a in rows))
            # the device's last token is the round's, taken or not
            self.tok[slot] = block[slot, count]
        return True

    def drain(self):
        while self.step():
            pass


class ManualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


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

    def _work(self, rng, lm):
        """``EARLY + LATE`` as ``(prompt, max_new_tokens, seed)``; a round
        verifies a position past the end, which a request leaves free."""
        slack = 1 if lm.mtp else 0
        return [(p, min(m, self.MAX_LEN - len(p) - slack), seed)
                for seed, (p, m) in enumerate(zip(
                    _prompts(rng, [n for n, _ in self.EARLY + self.LATE]),
                    [m for _, m in self.EARLY + self.LATE]))]

    @staticmethod
    def _same_record(req, want):
        """The routing rows, the selections and the drafts a request kept
        (``record_routing``) are ``want``'s."""
        assert req.drafts == want.drafts
        assert bool(req.routing) == bool(want.routing)
        if req.routing:     # a row a position but the last token's
            got = [np.concatenate(x, axis=1) for x in zip(*req.routing)]
            ref = [np.concatenate(x, axis=1) for x in zip(*want.routing)]
            assert got[0].shape[1] == len(req.prompt) + len(req.tokens) - 1
            for g, w in zip(got, ref):
                assert np.array_equal(g, w)
        assert bool(req.selection) == bool(want.selection)
        if req.selection:   # of the query that emitted each token
            got = np.concatenate(req.selection, axis=1)
            assert got.shape[1] == len(req.tokens)
            assert np.array_equal(got, np.concatenate(want.selection, axis=1))

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize("kind", list(KINDS))
    def test_equals_the_synchronous_loop(self, rng, monkeypatch, kind,
                                         sampled):
        """Token for token, key for key, routing row for routing row,
        selection for selection and draft for draft what the synchronous
        loop gives the same requests in the same admission order, with
        admissions mid-stream — whatever a slot holds, a prompt admitted
        whole or a block a step."""
        lm, _ = _kind(monkeypatch, kind)
        work = self._work(rng, lm)
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
        assert srv._unread is not None and srv._owed()
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
                self._same_record(req, ref_req)
        # a slot's key froze with its last request's last token
        last = {r.slot: i for i, r in enumerate(reqs)}
        for slot, i in last.items():
            cursor, tok, key = srv.engine.slot_state(slot)
            end = len(reqs[i].prompt) + len(reqs[i].tokens) - 1
            if lm.mtp:
                # a draft accepted past the end moves the cursor on, and a
                # sampled round splits every slot's key, frozen or not
                assert cursor in (end, end + 1)
            else:
                assert np.array_equal(key, want[i].keys[-1])
                assert (cursor, tok) == (end, reqs[i].tokens[-1])
        assert srv.steps == srv.stats()["decode_dispatches"]

    def _until_all_live(self, srv):
        """Step until a block dispatched for all three slots is unread (the
        prompts of a model prefilled in blocks enter one after another)."""
        while not (srv._unread and set(srv._unread[2]) == {0, 1, 2}):
            srv.step()

    @pytest.mark.parametrize("kind", list(KINDS))
    def test_deadline_and_cancel_with_a_block_in_flight(self, rng,
                                                        monkeypatch, kind):
        """A request shed on its deadline, and one canceled, while a block
        holding their next token is unread: the token is dropped, the
        slots stop decoding and are re-used, the others never notice."""
        lm, _ = _kind(monkeypatch, kind)
        prompts = _prompts(rng, (5, 9, 7, 6, 4))
        ref = self._reference(lm, False)
        want = [ref.submit(p, 8, s) for s, p in enumerate(prompts)]
        ref.drain()

        clock = ManualClock()
        srv = self._server(lm, False, clock=clock)
        keep = srv.submit(prompts[0], 8, seed=0)
        late = srv.submit(prompts[1], 8, seed=1, deadline_s=5.0)
        loser = srv.submit(prompts[2], 8, seed=2)
        self._until_all_live(srv)
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
                self._same_record(req, ref_req)

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
    def test_every_kind_is_read_one_dispatch_behind(self, rng, monkeypatch,
                                                    kind, sampled):
        """A plain step over whatever a slot holds, and a round of the
        model's own module: dispatched while the block before
        is unread, a server gives every request the tokens, the drafts, the
        selections and the routing rows of the synchronous order, with
        admissions mid-stream, slots re-used and requests that end inside a
        block."""
        lm, most = _kind(monkeypatch, kind)
        work = self._work(rng, lm)
        submit = [work[:len(self.EARLY)], [], [], [], work[len(self.EARLY):]]
        want, sync = self._run(self._server(lm, sampled), submit, True)
        srv = self._server(lm, sampled)
        reqs, spans = self._run(srv, submit, False)
        assert not any(sp["ahead"] for sp in sync) and sum(
            sp["ahead"] for sp in spans) >= len(spans) - 4
        assert len({r.slot for r in reqs}) < len(reqs)     # slots re-used
        for req, ref_req in zip(reqs, want):
            assert req.state == "finished"
            assert len(req.tokens) == req.max_new_tokens
            assert req.tokens == ref_req.tokens
            self._same_record(req, ref_req)
        assert (kind == "module") == (reqs[0].drafts is not None)
        assert (kind == "sparse") == (reqs[0].selection is not None)
        # the host's cursors are the device's once every block is read,
        # and the slots a dispatch served are no fewer than the rows that
        # yielded a token
        assert np.array_equal(srv._cursors,
                              np.asarray(srv.engine.cache.loop["cursors"]))
        st = srv.stats()
        if kind == "sparse":    # prompts of two and of three blocks
            assert st["prefill_blocks"] > len(work)
        assert st["decode_tokens"] == sum(m - 1 for _, m, _ in work)
        assert st["decode_tokens"] <= most * srv.slot_dispatches
        if not srv.engine.spec:
            assert st["empty_dispatches"] == 0

    @pytest.mark.parametrize("kind", list(KINDS))
    def test_ahead_of_a_lone_request(self, rng, monkeypatch, kind):
        """``ahead`` reads 0, 1, 1, ..., 0 on every kind: the first
        dispatch had no block before it, and the last span only reads."""
        lm, most = _kind(monkeypatch, kind)
        srv = self._server(lm, False)
        _, spans = self._run(srv, [[(_prompts(rng, (5,))[0], 21, 0)]], False)
        n = srv.stats()["decode_dispatches"]
        assert n >= -(-20 // most) and len(spans) == n + 1
        assert [sp["ahead"] for sp in spans] == [0] + [1] * (n - 1) + [0]
        assert [sp["live"] for sp in spans] == [1] * n + [0]
        assert [sp["kind"] for sp in spans] == [srv._decode_kind] * (n + 1)
        assert srv.stats()["decode_ahead_share"] == round((n - 1) / n, 4)

    @pytest.mark.parametrize("kind", list(KINDS))
    def test_sweep_and_cancel_with_steps_or_rounds_unread(self, rng,
                                                          monkeypatch, kind):
        """``test_deadline_and_cancel_with_a_block_in_flight`` against a
        server of its own kind, so that the drafts and the cursors are held
        too: the rows of a request shed on its deadline and of one
        canceled, in a block that is unread, are dropped; the requests
        admitted into their slots before that block is read take none of
        its tokens, and the slots' cursors are theirs."""
        lm, most = _kind(monkeypatch, kind)
        prompts = _prompts(rng, (5, 9, 7, 6, 4))
        ref = self._server(lm, False)
        want = [ref.submit(p, 14, seed=s) for s, p in enumerate(prompts)]
        ref.drain()

        clock = ManualClock()
        srv = self._server(lm, False, clock=clock)
        keep = srv.submit(prompts[0], 14, seed=0)
        late = srv.submit(prompts[1], 14, seed=1, deadline_s=5.0)
        loser = srv.submit(prompts[2], 14, seed=2)
        self._until_all_live(srv)
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
    def test_a_slot_is_free_once_its_last_block_is_dispatched(
            self, rng, monkeypatch, kind):
        """Reading a block one dispatch behind costs a request's slot
        nothing: the slot is free when the block that is certain to end
        its request is dispatched, so the next tenant enters at the step
        it enters in the synchronous order, while that block is unread;
        the request that left books its last tokens, its TPOT from its
        own clock, and retires one read later."""
        lm, most = _kind(monkeypatch, kind)
        pa, pb = _prompts(rng, (5, 7))
        tpot = metrics().histogram("serve_tpot_seconds")

        def run(sync):
            clock = ManualClock()
            srv = DecodeServer(lm, slots=1, max_len=self.MAX_LEN,
                               buckets=self.BUCKETS, clock=clock)
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

    def test_a_round_needs_a_row_past_the_last_position(self, rng):
        """A round writes its draft's row one past the cursor, accepted or
        not: ``submit()`` keeps that row free of the slot's capacity for a
        model that decodes in rounds, and for no other."""
        prompt = _prompts(rng, (20,))[0]
        srv = self._server(_module_lm(), False)
        with pytest.raises(ValueError, match="speculative slack"):
            srv.submit(prompt, 12)                  # 32 + 1 > 32
        req = srv.submit(prompt, 11)
        srv.drain()
        assert req.state == "finished" and len(req.tokens) == 11
        assert srv.engine.slot_state(req.slot)[0] <= self.MAX_LEN - 1
        plain = self._server(_lm("rope", max_len=32), False)
        assert plain.submit(prompt, 12) is not None

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
                 else srv.engine.decode_spec)
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


class TickClock:
    """A clock that moves on by one at every reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


class TestAdmission:
    """ISSUE 48: an admission is ONE device program (the prefill and the
    slot's loop state, its key made inside it), and its first token is
    read behind the step's decode dispatch, in the step that admitted."""

    SLOTS, MAX_LEN, BUCKETS = 3, 32, (8, 16, 32)
    SAMPLING = dict(temperature=0.8, top_k=7)
    # 2**31 + 5 and -3: PRNGKey keeps a Python int's low 32 bits
    SEEDS = (0, 7, 2**31 + 5, -3)

    def _server(self, lm, sampled=False, **kw):
        kw.setdefault("record_routing", bool(lm.num_experts))
        return DecodeServer(lm, slots=self.SLOTS, max_len=self.MAX_LEN,
                            buckets=self.BUCKETS,
                            **(self.SAMPLING if sampled else {}), **kw)

    @pytest.mark.parametrize("seed", [0, 1, 7, 2**31 - 1, 2**31 + 5,
                                      2**32 + 3, -3])
    def test_seed_key_is_prngkey(self, seed):
        """The key a block prefill is handed from the host, and the one the
        admission program makes from the low word: ``PRNGKey(seed)``."""
        import jax
        from deeplearning4j_tpu.serving.engine import seed_key

        want = np.asarray(jax.random.PRNGKey(seed))
        assert np.array_equal(seed_key(seed), want)
        assert seed_key(seed).dtype == want.dtype
        assert np.array_equal(want, jax.jit(jax.random.PRNGKey)(
            np.int64(seed).astype(np.int32)))

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize("kind", ["dense", "routed"])
    def test_one_program_is_prefill_then_admit_slot(self, rng, kind,
                                                    sampled):
        """``engine.admit`` against ``engine.prefill`` + ``admit_slot``:
        the same first token, routing, loop state (key included) and
        pool, over several seeds, slots and rungs."""
        import jax
        from deeplearning4j_tpu.serving.engine import DecodeEngine

        lm = KINDS[kind][0]()
        kw = dict(max_len=self.MAX_LEN, buckets=self.BUCKETS,
                  **(self.SAMPLING if sampled else {}))
        two, one = (DecodeEngine(lm, self.SLOTS, **kw) for _ in range(2))
        work = zip(_prompts(rng, (5, 11, 20, 3)), (2, 0, 1, 2), (9, 1, 12, 4),
                   self.SEEDS)
        for prompt, slot, new, seed in work:
            tok, key, routing = two.prefill(prompt, slot,
                                            jax.random.PRNGKey(seed))
            two.admit_slot(slot, tok, len(prompt), new - 1, key)
            got, got_routing = one.admit(prompt, slot, new, seed)
            assert int(got) == int(tok)
            assert (routing is None) == (got_routing is None) == (
                kind == "dense")
            if routing is not None:
                assert np.array_equal(got_routing, routing)
            for name, want in two.cache.loop.items():
                assert np.array_equal(one.cache.loop[name], want), name
            assert int(one.cache.loop["remaining"][slot]) == new - 1
            assert jax.tree_util.tree_all(jax.tree_util.tree_map(
                np.array_equal, one.cache.state, two.cache.state))
        # one program a rung, and the release program behind the first
        assert one.compile_counts() == {
            "decode": 0, "prefill_buckets": [8, 16, 32], "total": 4}

    def test_block_prefill_keeps_its_programs(self):
        """A model prefilled in blocks is admitted a block a step."""
        from deeplearning4j_tpu.serving.engine import DecodeEngine

        with pytest.raises(ValueError, match="a block a step"):
            DecodeEngine(_module_lm(), 2, max_len=32).admit(
                np.arange(1, 6, dtype=np.int32), 0, 4, 0)

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize("kind", ["dense", "routed"])
    def test_answers_are_the_synchronous_loops(self, rng, kind, sampled):
        """Whole answers through the server against the loop that admits
        with ``engine.prefill`` and keys made on the host: one token, two
        and many, several seeds, two admissions in one step."""
        lm = KINDS[kind][0]()
        news = (1, 2, 9, 1, 12, 2, 6, 5)
        work = list(zip(_prompts(rng, (5, 11, 7, 3, 20, 9, 4, 6)), news,
                        self.SEEDS * 2))
        ref = SyncLoop(lm, self.SLOTS, self.MAX_LEN, buckets=self.BUCKETS,
                       **(self.SAMPLING if sampled else {}))
        want = [ref.submit(p, m, seed) for p, m, seed in work]
        ref.drain()
        srv = self._server(lm, sampled)
        reqs = [srv.submit(p, m, seed=seed) for p, m, seed in work]
        srv.step()
        # three admissions in the first step, their first tokens booked in
        # it; the one-token request is out of its slot already
        assert [len(r.tokens) for r in reqs[:4]] == [1, 1, 1, 0]
        assert reqs[0].state == "finished" and srv._slot_req[0] is None
        assert not srv._pending
        srv.drain()
        for req, ref_req in zip(reqs, want):
            assert req.state == "finished"
            assert req.tokens == ref_req.tokens
            if lm.num_experts:
                TestPipelinedLoop._same_record(req, ref_req)
        assert srv.stats()["finished"] == len(work)

    def test_a_lone_one_token_request(self, rng):
        """Nothing owes a token: no decode dispatch, the first token is
        read all the same, in the step that admitted, and no block was
        dispatched behind it."""
        srv = self._server(_lm("rope", max_len=32))
        req = srv.submit(_prompts(rng, (5,))[0], 1)
        assert srv.step() and req.state == "finished"
        assert len(req.tokens) == 1 and req.first_token_s == req.finish_s
        assert not srv.busy() and not srv.step()
        st = srv.stats()
        assert (st["admit_ahead"], st["decode_dispatches"]) == (0, 0)

    def test_a_deadline_that_passes_with_the_first_token_pending(self, rng):
        """A first token is pending only inside the step that admitted:
        a deadline that passes meanwhile sheds the request at the next
        step's sweep, its first token booked, the block that holds its
        second dropped; the slot's next tenant never notices."""
        lm = _lm("rope", max_len=32)
        prompts = _prompts(rng, (5, 9, 7))
        ref = SyncLoop(lm, self.SLOTS, self.MAX_LEN, buckets=self.BUCKETS)
        want = [ref.submit(p, 8, s) for s, p in enumerate(prompts)]
        ref.drain()
        clock = TickClock()
        srv = self._server(lm, clock=clock)
        keep = srv.submit(prompts[0], 8, seed=0)
        # alive when it is popped, past its deadline when its token is read
        late = srv.submit(prompts[1], 8, seed=1, deadline_s=clock.t + 5.5)
        srv.step()
        assert late.state == "running" and len(late.tokens) == 1
        assert late.first_token_s > late.deadline_s
        nxt = srv.submit(prompts[2], 8, seed=2)
        srv.step()
        assert late.state == "shed" and srv.expired_in_flight == 1
        assert nxt.slot == late.slot
        srv.drain()
        assert late.tokens == want[1].tokens[:1]
        for req, ref_req in ((keep, want[0]), (nxt, want[2])):
            assert req.state == "finished" and req.tokens == ref_req.tokens

    def test_admission_sends_its_arguments_and_launches_one_program(self,
                                                                    rng):
        """Beside PR 26's guard on the decode step: after warm-up an
        admission launches exactly one program — and the step's decode
        program, where a slot owes a token — compiles nothing, and sends
        the host's values nowhere but into that program's call: under
        ``disallow_explicit``, which refuses every transfer, a jitted call's
        own NumPy arguments too (the prompt has to travel), the guard is
        lifted inside the engine's programs alone, so a ``jnp.asarray``, a
        ``device_put`` or an eager ``PRNGKey`` beside them fails."""
        import jax

        srv = self._server(_lm("rope", max_len=32))
        for p in _prompts(rng, (5, 12)):
            srv.submit(p, 3)
        srv.drain()
        if _executions(lambda: srv.engine.release_slot(0)) != 1:
            pytest.skip("this jax's CPU trace does not show launches")
        builds = srv.engine.program_builds
        programs = dict(srv.engine._programs)
        sizes = {sig: fn._cache_size() for sig, fn in programs.items()}

        def lifted(fn):
            def run(*args):
                with jax.transfer_guard_host_to_device("allow"):
                    return fn(*args)
            return run

        srv.engine._programs.update(
            {sig: lifted(fn) for sig, fn in programs.items()})
        one, many, more = (srv.submit(p, m, seed=s) for s, (p, m) in
                           enumerate(zip(_prompts(rng, (4, 7, 11)),
                                         (1, 9, 9))))
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            with pytest.raises(Exception, match="Disallowed host-to-device"):
                jax.random.PRNGKey(0)
            # the engine's one program of an admission, nothing else
            assert _executions(lambda: srv.engine.admit(
                one.prompt, 2, 1, 0)) == 1
            srv.engine.release_slot(2)
            # three admissions and the decode dispatch behind them
            assert _executions(srv.step) == 4
            srv.drain()
        assert [len(r.tokens) for r in (one, many, more)] == [1, 9, 9]
        assert srv.engine.program_builds == builds
        assert sizes == {sig: fn._cache_size()
                         for sig, fn in programs.items()}

    def test_the_order_of_a_step_that_admits(self, rng):
        """From the tracer's spans, in a step that admits with a slot
        live: ``serve.prefill`` closes (inside ``serve.admit``) before
        ``serve.decode`` opens, ``serve.first_token`` opens after the
        decode dispatch and inside the same ``serve.step``,
        ``admit_ahead`` counts the admission, and the routing of the
        prompt is booked on ``serve.first_token`` — ``serve.decode``
        carries the decode block's ``experts_touched``."""
        lm = _routed_lm()
        cells = lm.n_layers("moe") * lm.experts_per_token   # a live row's
        ahead0 = metrics().counter("serve_admit_ahead_total").value()
        tr = SpanTracer(clock=TickClock())
        set_tracer(tr)
        try:
            srv = self._server(lm)
            first = srv.submit(_prompts(rng, (5,))[0], 12, seed=0)
            for _ in range(3):
                srv.step()
            tr.clear()
            req = srv.submit(_prompts(rng, (20,))[0], 6, seed=1)
            srv.step()
            spans = {sp.name: sp for sp in tr.spans()}
            srv.drain()
        finally:
            set_tracer(None)
        step, admit, prefill, decode, token, emit = (
            spans["serve." + n] for n in (
                "step", "admit", "prefill", "decode", "first_token", "emit"))
        assert admit.start_s < prefill.start_s < prefill.end_s < admit.end_s
        assert admit.end_s < decode.start_s < decode.end_s < token.start_s
        assert token.end_s < emit.start_s < emit.end_s < step.end_s
        assert token.parent_id == decode.parent_id == step.span_id
        assert prefill.attrs["prompt_len"] == 20
        assert "experts_touched" not in prefill.attrs
        assert (decode.attrs["live"], decode.attrs["ahead"]) == (2, 1)
        assert (token.attrs["request"], token.attrs["slot"],
                token.attrs["ahead"]) == (req.id, req.slot, 1)
        # one live slot's row in the block read; the prompt's 20 rows
        assert 0 < decode.attrs["experts_touched"] <= cells
        experts = req.routing[0][0]                     # [L, 20, k]
        assert token.attrs["experts_touched"] == sum(
            len(np.unique(layer)) for layer in experts) > cells
        # the lone first request too: its first decode block was behind it
        assert srv.stats()["admit_ahead"] == 2
        assert metrics().counter(
            "serve_admit_ahead_total").value() == ahead0 + 2
        assert len(first.tokens) == 12 and len(req.tokens) == 6


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
        srv.engine._decode_jit = lambda impl, *bound: (
            eng.DecodeEngine._decode_jit(
                srv.engine,
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

    def test_tokens_before_and_after_a_slot_changes_tenant(
            self, monkeypatch):
        want = self._serve(5, self._server(monkeypatch, False))
        srv = self._server(monkeypatch, True)
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
