"""Driver ``lm_serve``: ``DecodeServer`` under an open loop on one chip.

One thread offers the seeded schedule (``lib/loadgen.py``) and steps the
server, the way ``serving/loadgen.run_open_loop`` drives it, but every latency
counts from the request's **due** instant. Two shapes of window, chosen by
``loop.cut_at_seconds`` in the workload file:

- false (below the knee): offer for ``--seconds``, then drain, so that every
  request offered has an outcome; the latencies are the metrics.
- true (above the knee): stop hard at ``--seconds``; the metric is the tokens
  of requests completed inside the window per second.

Set-up warms the decode program, every prefill rung the traffic's prompt
lengths can reach, and every slot. After the window the server goes out of
scope, which frees its pool, and a seeded sample of finished requests is
checked against the plain reference.

From the program the driver uses ``DecodeServer``'s constructor, ``submit`` /
``try_submit`` / ``step`` / ``busy`` / ``drain``, ``stats()``, the counters
``steps`` / ``slot_dispatches`` / ``decode_tokens`` and the request objects'
``tokens`` / ``state`` / ``first_token_s`` / ``finish_s``; nothing with a
leading underscore.

Workload file keys: ``server.{slots,max_len,buckets,max_queue,policy}``,
``traffic`` (see ``lib/loadgen.py``), ``loop.cut_at_seconds``,
``check.{near_tie,sample_short,short_max_prompt}``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.drivers import _lm_common as common
from benchmarks.lib import loadgen, reference_lm
from benchmarks.lib.outcome import Outcome

INF = float("inf")


def build_model(ctx):
    sv = ctx.cell["server"]
    lm = common.build_lm(ctx.config, policy=sv["policy"], seed=ctx.seed,
                         max_len=int(sv["max_len"]))
    lm.params, _ = common.make_params(lm, ctx.seed)
    return lm


def build_server(ctx, lm):
    from deeplearning4j_tpu.serving import DecodeServer

    sv = ctx.cell["server"]
    return DecodeServer(lm, slots=int(sv["slots"]),
                        max_queue=int(sv["max_queue"]),
                        max_len=int(sv["max_len"]),
                        buckets=tuple(sv["buckets"]), fuse_steps=1,
                        clock=time.monotonic)


def warm_up(server, buckets, traffic, rng) -> None:
    """The decode program, each prefill rung a prompt of this traffic can
    land on (a prompt goes to the smallest rung that holds it), and every
    slot's bookkeeping."""
    vocab = server.model.vocab_size
    lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    top = min(b for b in buckets if b >= hi)
    for b in sorted(b for b in buckets if lo <= b <= top):
        server.submit(rng.integers(1, vocab, min(b, hi), np.int32), 2)
    server.drain()
    for _ in range(server.slots):
        server.submit(rng.integers(1, vocab, lo, np.int32), 2)
    server.drain()


def request_times(res, offered):
    """``(ttft_s, tpot_s or None, finished)`` of one offered request, from
    its due instant."""
    req = offered.request
    if req is None or req.first_token_s is None:
        return INF, None, False
    ttft = req.first_token_s - (res.t0 + offered.arrival.due_s)
    done = (req.state == "finished"
            and len(req.tokens) == offered.arrival.max_new_tokens)
    tpot = None
    if done and len(req.tokens) > 1:
        tpot = (req.finish_s - req.first_token_s) / (len(req.tokens) - 1)
    return ttft, tpot, done


def check_against_reference(lm, cfg, finished, check, rng):
    """Each generated token of a seeded sample of finished requests must be
    the argmax of the reference's teacher-forced logits, or within
    ``near_tie`` x max|logit| of it."""
    short = [o for o in finished
             if len(o.arrival.prompt) <= check["short_max_prompt"]]
    picks = [short[j] for j in rng.permutation(len(short))
             [:check["sample_short"]]]
    longest = max(finished, key=lambda o: len(o.arrival.prompt))
    if longest not in picks:
        picks.append(longest)
    notes, ok = [], True
    for o in picks:
        toks = np.asarray(o.request.tokens, np.int32)
        seq = np.concatenate([o.arrival.prompt, toks])[:-1]
        n = len(toks)
        n_tail = min(len(seq), 256)
        pad_to = max(256, 1 << int(np.ceil(np.log2(len(seq)))))
        logits = np.asarray(reference_lm.tail_logits(
            lm.params, seq, cfg, n_tail, pad_to=pad_to))[-n:]
        best = logits.max(axis=-1)
        gap = (best - logits[np.arange(n), toks]) / np.abs(logits).max(-1)
        bad = int(np.sum(gap > check["near_tie"]))
        ok &= bad == 0
        notes.append(f"check: prompt={len(o.arrival.prompt)} new={n} "
                     f"off_argmax={int(np.sum(gap > 0))} "
                     f"worst_gap={float(gap.max()):.5f} "
                     f"beyond_near_tie={bad}")
    return ok, notes


def serve_window(ctx, lm, cut):
    """Build the server, warm it, offer the schedule. The server is local to
    this function: when it returns nothing holds the pool any more."""
    cell = ctx.cell
    server = build_server(ctx, lm)
    rng = np.random.default_rng([ctx.seed, 0x5E7])
    with ctx.spans.span("warmup"):
        warm_up(server, cell["server"]["buckets"], cell["traffic"], rng)
    schedule = loadgen.make_schedule(cell["traffic"], ctx.seed, ctx.seconds,
                                     lm.vocab_size)
    before = server.stats()
    slot0 = server.slot_dispatches
    marks = {}        # the server's counters when tracing began and ended

    def on_step(_now):
        ctx.tick()
        if ctx.trace_state not in marks:
            marks[ctx.trace_state] = (server.steps, server.slot_dispatches)

    ctx.begin_window()
    res = loadgen.run_open_loop(
        server, schedule, cut_s=ctx.seconds if cut else None,
        on_step=on_step, step_span=lambda: ctx.spans.span("serve.step"))
    ctx.end_window()
    after = server.stats()
    now = (server.steps, server.slot_dispatches)
    # occupancy from before the profiler started (it stalls the host when
    # it starts and stops); the decode steps under the profiler tell the
    # trace readers which program is decode
    steps1, slot1 = marks.get("on", now)
    counters = {
        "decode_steps": after["steps"] - before["steps"],
        "decode_tokens": after["decode_tokens"] - before["decode_tokens"],
        "slot_occupancy_pct": (100.0 * (slot1 - slot0) / max(
            1, (steps1 - before["steps"]) * after["slots"])),
        "program_builds_in_window": (after["compiles"]["total"]
                                     - before["compiles"]["total"]),
        "shed_in_window": after["shed"] - before["shed"],
        "queue_depth_at_end": after["queue_depth"],
    }
    if "on" in marks:
        counters["decode_steps_in_trace"] = (marks.get("done", now)[0]
                                             - marks["on"][0])
    return res, counters, rng


def run(ctx) -> Outcome:
    cfg, cell = ctx.config, ctx.cell
    cut = bool(cell["loop"]["cut_at_seconds"])
    lm = build_model(ctx)
    res, counters, rng = serve_window(ctx, lm, cut)
    gc.collect()

    times = [request_times(res, o) for o in res.offered]
    finished = [o for o, x in zip(res.offered, times) if x[2]]
    refused = sum(1 for o in res.offered if o.request is None)
    shed = counters["shed_in_window"]
    # in flight at a hard cut: neither tokens nor failures; after a drain
    # whatever did not finish failed, and counts as +inf in the tails
    failed = refused + shed if cut else len(res.offered) - len(finished)
    # the profiler stalls the host when it starts and stops, so a traced
    # run's host-clock numbers are of what was over before it started
    # (trace_t0 is +inf in an untraced run: everything)
    ttft = [x[0] for o, x in zip(res.offered, times)
            if (x[0] != INF and o.request.first_token_s <= ctx.trace_t0)
            or (x[0] == INF and not cut)]
    tpot = [x[1] for o, x in zip(res.offered, times)
            if x[1] is not None and o.request.finish_s <= ctx.trace_t0]
    if not cut:
        tpot += [INF] * failed
    late = [o.late_s for o in res.offered
            if res.t0 + o.arrival.due_s <= ctx.trace_t0]
    done_tokens = sum(len(o.request.tokens) for o in finished)
    counters.update({
        "requests_offered": len(res.offered),
        "requests_finished": len(finished),
        "tokens_finished": done_tokens,
        "gen_late_p95_ms": 1e3 * loadgen.percentile(late, 95),
        "ttft_p50_ms": 1e3 * loadgen.percentile(ttft, 50),
        "ttft_p95_ms": 1e3 * loadgen.percentile(ttft, 95),
        "tpot_p50_ms": 1e3 * loadgen.percentile(tpot, 50),
        "tpot_p95_ms": 1e3 * loadgen.percentile(tpot, 95),
        "drain_s": res.drain_s,
        "window_s": res.window_s,
    })
    notes = [f"serve: offered={len(res.offered)} finished={len(finished)} "
             f"refused={refused} shed={shed} window_s={res.window_s:.3f} "
             f"drain_s={res.drain_s:.3f} steps={counters['decode_steps']} "
             f"tokens_finished={done_tokens} "
             f"ttft_ms p50={counters['ttft_p50_ms']:.2f} "
             f"p95={counters['ttft_p95_ms']:.2f} (n={len(ttft)}) "
             f"tpot_ms p50={counters['tpot_p50_ms']:.3f} "
             f"p95={counters['tpot_p95_ms']:.3f} (n={len(tpot)}) "
             f"gen_late_p95_ms={counters['gen_late_p95_ms']:.3f} "
             f"queue_at_end={counters['queue_depth_at_end']}"]

    # ---- correct: the pool is gone, so the reference has room
    ok = counters["program_builds_in_window"] == 0 and bool(finished)
    if not cut:
        ok &= failed == 0
    if finished:
        ref_ok, ref_notes = check_against_reference(
            lm, cfg, finished, cell["check"], rng)
        ok &= ref_ok
        notes += ref_notes
    return Outcome(
        correct=ok, attempted=len(res.offered), failed=failed,
        end_to_end={"serve_tpot_p50_ms": counters["tpot_p50_ms"],
                    "serve_tok_per_s": done_tokens / res.window_s},
        counters=counters, notes=notes)
