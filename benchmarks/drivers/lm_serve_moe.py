"""Driver ``lm_serve_moe``: ``lm_serve``'s open loop for a model with routed
experts (OLMoE), on one chip.

The warm-up, the schedule, the clock and the request times are ``lm_serve``'s,
by import. This driver brings what the model changes: its builder
(``_moe_common``), a server that records which experts served every position
(``DecodeServer(record_routing=True)``), the expert load the server booked
during the window (``stats()["moe_expert_load"]``, and ``experts_touched`` of
the program's ``serve.decode`` spans), and a reference check that judges what
the window computed, routing included (``lib/reference_olmoe.py``).

Activations rounded to bf16 flip the router's near-ties, and one flipped
expert exchanges an eighth of a token's feed-forward for every later position
to attend to, so a reference left to its own choices drifts from the served
sequence within a few tokens. The check therefore takes, for a seeded sample
of finished requests, the experts the prefill and decode programs of the
window chose at every position (``request.routing``), and holds them and what
followed from them to the float32 reference:

- routing: at every position and layer no expert the server used falls short
  of the reference's own k-th probability by more than ``route_gap`` of it
  (its own top k has shortfall 0; a rounded activation can swap experts that
  close, and nothing else), and the server's weights are the reference's
  probabilities of the same experts to ``weight_rel_tol`` (a renormalised
  weight is off by the factor 1 / (sum of the chosen eight)). The reference's
  probabilities are its own, float32, at each layer of a forward that uses the
  served experts below it (``forward_tail(chosen=)``);
- tokens: **every** generated token is the argmax of that forward's
  teacher-forced float32 logits, or within ``near_tie`` x max|logit| of it
  (as ``lm_serve``).

The share of (token, layer) pairs served outside the reference's top k is
printed.

Workload file keys: those of ``lm_serve`` and ``check.{route_gap,
weight_rel_tol}``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.drivers import _moe_common as common
from benchmarks.drivers import lm_serve
from benchmarks.lib import loadgen, reference_olmoe
from benchmarks.lib.outcome import Outcome

INF = float("inf")


def build_model(ctx):
    sv = ctx.cell["server"]
    lm = common.build_lm(ctx.config, policy=sv["policy"], seed=ctx.seed,
                         max_len=int(sv["max_len"]))
    lm.params = common.make_params(lm, ctx.seed)
    return lm


def build_server(ctx, lm):
    """``lm_serve.build_server``'s server, recording its routing."""
    from deeplearning4j_tpu.serving import DecodeServer

    sv = ctx.cell["server"]
    return DecodeServer(lm, slots=int(sv["slots"]),
                        max_queue=int(sv["max_queue"]),
                        max_len=int(sv["max_len"]),
                        buckets=tuple(sv["buckets"]), fuse_steps=1,
                        clock=time.monotonic, record_routing=True)


def serve_window(ctx, lm):
    """``lm_serve.serve_window`` for a cell that drains, with the server's
    expert load over the window among the counters. The server is local to
    this function: when it returns nothing holds the pool any more."""
    from deeplearning4j_tpu.monitor import trace as program_trace

    cell = ctx.cell
    server = build_server(ctx, lm)
    rng = np.random.default_rng([ctx.seed, 0x5E7])
    with ctx.spans.span("warmup"):
        lm_serve.warm_up(server, cell["server"]["buckets"], cell["traffic"],
                         rng)
    schedule = loadgen.make_schedule(cell["traffic"], ctx.seed, ctx.seconds,
                                     lm.vocab_size)
    before = server.stats()
    slot0 = server.slot_dispatches
    marks = {}        # the server's counters when tracing began and ended
    touched = []      # experts_touched of every decode step of the window

    def on_step(_now):
        ctx.tick()
        if ctx.trace_state not in marks:
            marks[ctx.trace_state] = (server.steps, server.slot_dispatches)

    def sink(span):
        reached = span["attrs"].get("experts_touched")
        if span["name"] == "serve.decode" and reached is not None:
            touched.append(reached)

    program_trace.add_sink(sink)
    try:
        ctx.begin_window()
        res = loadgen.run_open_loop(
            server, schedule, on_step=on_step,
            step_span=lambda: ctx.spans.span("serve.step"))
        ctx.end_window()
    finally:
        program_trace.remove_sink(sink)
    after = server.stats()
    now = (server.steps, server.slot_dispatches)
    # as lm_serve: occupancy from before the profiler started; the decode
    # steps under the profiler tell the trace readers which program is decode
    steps1, slot1 = marks.get("on", now)
    steps = after["steps"] - before["steps"]
    load = (np.asarray(after["moe_expert_load"])
            - np.asarray(before["moe_expert_load"]))        # [layers, experts]
    counters = {
        "decode_steps": steps,
        "decode_tokens": after["decode_tokens"] - before["decode_tokens"],
        "slot_occupancy_pct": (100.0 * (slot1 - slot0) / max(
            1, (steps1 - before["steps"]) * after["slots"])),
        "program_builds_in_window": (after["compiles"]["total"]
                                     - before["compiles"]["total"]),
        "shed_in_window": after["shed"] - before["shed"],
        "queue_depth_at_end": after["queue_depth"],
        "moe_routed_pairs": int(load.sum()),
        # the busiest expert's pairs over its layer's mean, worst layer
        "moe_load_max_over_mean": float(
            (load.max(axis=1) / np.maximum(load.mean(axis=1), 1e-9)).max()),
        # (layer, expert) cells a decode step's live slots reached, mean
        "moe_experts_touched_per_step": (float(np.mean(touched))
                                         if touched else 0.0),
        "moe_live_slots_per_step": (now[1] - slot0) / max(1, steps),
    }
    if "on" in marks:
        counters["decode_steps_in_trace"] = (marks.get("done", now)[0]
                                             - marks["on"][0])
    return res, counters, rng


def check_against_reference(lm, cfg, finished, check, traffic, rng):
    """Routing and tokens of a seeded sample of finished requests, as the
    window's programs computed them, against the plain reference; see the
    module's docstring."""
    short = [o for o in finished
             if len(o.arrival.prompt) <= check["short_max_prompt"]]
    picks = [short[j] for j in rng.permutation(len(short))
             [:check["sample_short"]]]
    longest = max(finished, key=lambda o: len(o.arrival.prompt))
    if longest not in picks:
        picks.append(longest)
    # one length for every sequence, one tail for every answer: the
    # reference compiles once
    pad_to = int(traffic["max_total_tokens"])
    n_tail = int(traffic["output_tokens"]["max"])
    notes, ok = [], True
    flipped = pairs = 0
    for o in picks:
        toks = np.asarray(o.request.tokens, np.int32)
        seq = np.concatenate([o.arrival.prompt, toks])[:-1]
        n = len(toks)
        # [L, T, k]: the prompt's rows from the prefill program, then one
        # row from each decode step that emitted a token but the last
        experts, weights = (np.concatenate(x, axis=1)
                            for x in zip(*o.request.routing))
        if experts.shape[1] != len(seq):
            raise RuntimeError(f"request {o.request.id}: {experts.shape[1]} "
                               f"rows of routing for {len(seq)} positions")
        logits, routes = reference_olmoe.forward_tail(
            lm.params, seq, cfg, n_tail, pad_to=pad_to, chosen=experts)
        logits = np.asarray(logits)[-n:]
        best = logits.max(axis=-1)
        gap = (best - logits[np.arange(n), toks]) / np.abs(logits).max(-1)
        bad = int(np.sum(gap > check["near_tie"]))
        shortfall = np.stack([np.asarray(r[3]) for r in routes])  # [L, T]
        wrong = int(np.sum(shortfall > check["route_gap"]))
        w_ref = np.stack([np.asarray(r[0]) for r in routes])      # [L, T, k]
        w_rel = float((np.abs(weights - w_ref) / w_ref).max())
        ok &= bad == 0 and wrong == 0 and w_rel <= check["weight_rel_tol"]
        flipped += int(np.sum(shortfall > 0))
        pairs += shortfall.size
        notes.append(f"check: prompt={len(o.arrival.prompt)} new={n} "
                     f"judged={n} off_argmax={int(np.sum(gap > 0))} "
                     f"worst_gap={float(gap.max()):.5f} "
                     f"beyond_near_tie={bad} "
                     f"routes_beyond_route_gap={wrong} "
                     f"worst_shortfall={float(shortfall.max()):.5f} "
                     f"worst_weight_rel_diff={w_rel:.5f}")
    notes.append(f"check: routing flipped_share={flipped / pairs:.6f} "
                 f"({flipped} of {pairs} served (token, layer) pairs used an "
                 f"expert outside the reference's top "
                 f"{cfg['num_experts_per_tok']}; allowed up to a shortfall "
                 f"of route_gap={check['route_gap']})")
    return ok, notes


def run(ctx) -> Outcome:
    cfg, cell = ctx.config, ctx.cell
    if cell["loop"]["cut_at_seconds"]:
        raise SystemExit("lm_serve_moe drains: no cell of it cuts its window")
    lm = build_model(ctx)
    res, counters, rng = serve_window(ctx, lm)
    gc.collect()

    times = [lm_serve.request_times(res, o) for o in res.offered]
    finished = [o for o, x in zip(res.offered, times) if x[2]]
    refused = sum(1 for o in res.offered if o.request is None)
    # after a drain whatever did not finish failed, and counts as +inf
    failed = len(res.offered) - len(finished)
    # a traced run's host-clock numbers are of what was over before the
    # profiler started (trace_t0 is +inf in an untraced run: everything)
    ttft = [x[0] for o, x in zip(res.offered, times)
            if x[0] == INF or o.request.first_token_s <= ctx.trace_t0]
    tpot = [x[1] for o, x in zip(res.offered, times)
            if x[1] is not None and o.request.finish_s <= ctx.trace_t0]
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
             f"refused={refused} shed={counters['shed_in_window']} "
             f"window_s={res.window_s:.3f} drain_s={res.drain_s:.3f} "
             f"steps={counters['decode_steps']} "
             f"tokens_finished={done_tokens} "
             f"ttft_ms p50={counters['ttft_p50_ms']:.2f} "
             f"p95={counters['ttft_p95_ms']:.2f} (n={len(ttft)}) "
             f"tpot_ms p50={counters['tpot_p50_ms']:.3f} "
             f"p95={counters['tpot_p95_ms']:.3f} (n={len(tpot)}) "
             f"gen_late_p95_ms={counters['gen_late_p95_ms']:.3f} "
             f"queue_at_end={counters['queue_depth_at_end']}",
             f"moe: routed_pairs={counters['moe_routed_pairs']} "
             f"load_max_over_mean={counters['moe_load_max_over_mean']:.4f} "
             f"experts_touched_per_step="
             f"{counters['moe_experts_touched_per_step']:.2f} "
             f"live_slots_per_step="
             f"{counters['moe_live_slots_per_step']:.2f}"]

    # ---- correct: the pool is gone, so the reference has room
    ok = (counters["program_builds_in_window"] == 0 and bool(finished)
          and failed == 0)
    if finished:
        ref_ok, ref_notes = check_against_reference(
            lm, cfg, finished, cell["check"], cell["traffic"], rng)
        ok &= ref_ok
        notes += ref_notes
    return Outcome(
        correct=ok, attempted=len(res.offered), failed=failed,
        end_to_end={"serve_tpot_p50_ms": counters["tpot_p50_ms"]},
        counters=counters, notes=notes)
