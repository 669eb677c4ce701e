"""Driver ``lm_serve_hybrid``: ``lm_serve``'s open loop for the hybrid model
(``ling-3.0-flash-vl-l7``: KDA and MLA layers, group-limited sigmoid routing
with a shared expert, one chip's share of the experts and of the
vocabulary), on one chip.

The warm-up, the schedule, the clock and the request times are ``lm_serve``'s,
by import, and the open loop is ``lm_serve_moe``'s with this model's counters
beside its own. This driver brings what the model changes:

- its builder: ``TransformerLM`` described per layer from the configuration
  file (which published layers are kept, which of them are MLA, how many
  experts of the router's are held here), and its weights, made on the device
  from the seed one block at a time (11 GB in one program would need its
  temporaries beside them);
- the server's own counts of the window (``DecodeServer.stats()``): state
  bytes by kind, live slots a step, held experts a decode step reached, and
  the (token, expert) pairs that landed on an expert held here per live row
  and expert layer (expectation k x held / E = 1.0);
- the reference check (``lib/reference_ling.py``), as ``lm_serve_moe``'s: a
  seeded sample of finished requests is recomputed with the experts the
  window's own prefill and decode programs chose at every position, and
  judged on routing (``route_gap``, ``weight_rel_tol``) and on **every**
  generated token (the argmax of the float32 teacher-forced logits over the
  vocabulary slice, or within ``near_tie`` x max|logit| of it).

Workload file keys: those of ``lm_serve_moe``.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import lm_serve
from benchmarks.drivers._moe_common import _check_tree
from benchmarks.lib import loadgen, reference_ling
from benchmarks.lib.outcome import Outcome

INF = float("inf")


# ---- the model from its configuration file ----------------------------------
def layer_kinds(config: dict):
    """``(mixers, ffns)`` of the layers kept: a published layer whose index
    + 1 is a multiple of ``layer_group_size`` is MLA, the others KDA; the
    first ``first_k_dense_replace`` layers of the stack are dense."""
    period = config["layer_group_size"]
    mixers = ["mla" if (i + 1) % period == 0 else "kda"
              for i in config["kept_layers"]]
    dense = config["first_k_dense_replace"]
    return mixers, ["glu" if j < dense else "moe"
                    for j in range(len(mixers))]


def build_lm(config: dict, *, policy: str, seed: int, max_len: int):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    mixers, ffns = layer_kinds(config)
    if len(mixers) != config["num_hidden_layers"]:
        raise SystemExit("kept_layers and num_hidden_layers disagree")
    share = config["share"]
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_layers=len(mixers),
        d_ff=config["moe_intermediate_size"], max_len=max_len, seed=seed,
        dtype_policy=policy, pos_encoding="rope", norm="rmsnorm",
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_interleaved=True, tie_embeddings=config["tie_word_embeddings"],
        num_experts=config["published"]["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        mixers=mixers, ffns=ffns, glu_width=config["intermediate_size"],
        kda={"head_dim": config["head_dim"],
             "conv": config["short_conv_kernel_size"],
             "lower": float(config["kda_lower_bound"])},
        mla={k: config[k] for k in ("kv_lora_rank", "qk_nope_head_dim",
                                    "qk_rope_head_dim", "v_head_dim")},
        moe={"n_group": config["n_group"],
             "topk_group": config["topk_group"],
             "scale": config["routed_scaling_factor"],
             "bias": bool(config["moe_router_enable_expert_bias"]),
             "shared_width": config["moe_shared_expert_intermediate_size"],
             "first": share["first_expert"], "held": config["num_experts"]})


def reference_config(config: dict) -> dict:
    """What ``lib/reference_ling.py`` reads, from the configuration file."""
    keep = ("num_attention_heads", "rms_norm_eps", "rope_theta",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "n_group", "topk_group",
            "routed_scaling_factor", "kda_lower_bound")
    return {**{k: config[k] for k in keep},
            "share": {"first_expert": config["share"]["first_expert"],
                      "held": config["num_experts"]}}


def _block_init(lm, mixer: str, ffn: str):
    """``key -> block`` for one kind of layer: Glorot-normal matrices, unit
    gains, convolution taps normal / sqrt(taps), and the small vectors drawn
    too (A_log x 0.3, dt_bias x 0.5, expert bias x 0.01) so that no path
    sees only zeros."""
    d, h, dt = lm.d_model, lm.num_heads, lm.policy.param_dtype

    def glorot(key, shape, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dt)
        return jax.random.normal(key, shape, dt) * scale

    def dense(key, fan_in, fan_out):
        return glorot(key, (fan_in, fan_out), fan_in, fan_out)

    def normal(key, shape, scale):
        return jax.random.normal(key, shape, dt) * scale

    def gain(width=d):
        return {"g": jnp.ones((width,), dt)}

    def init(key):
        k = jax.random.split(key, 24)
        blk = {"ln1": gain(), "ln2": gain()}
        if mixer == "kda":
            dk, taps = lm.kda["head_dim"], lm.kda["conv"]
            c = h * dk
            blk["kda"] = {
                "wq": dense(k[0], d, c), "wk": dense(k[1], d, c),
                "wv": dense(k[2], d, c), "wa": dense(k[3], d, c),
                "wb": dense(k[4], d, h), "wg": dense(k[5], d, h),
                "wo": dense(k[6], c, d),
                "conv_q": normal(k[7], (taps, c), taps ** -0.5),
                "conv_k": normal(k[8], (taps, c), taps ** -0.5),
                "conv_v": normal(k[9], (taps, c), taps ** -0.5),
                "a_log": normal(k[10], (h,), 0.3),
                "dt_bias": normal(k[11], (c,), 0.5),
                "o_norm": gain(dk)}
        else:
            m = lm.mla
            r, dn, dr, dv = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                             m["qk_rope_head_dim"], m["v_head_dim"])
            blk["mla"] = {"wq": dense(k[0], d, h * (dn + dr)),
                          "wdkv": dense(k[1], d, r + dr),
                          "kv_norm": gain(r),
                          "wukv": dense(k[2], r, h * (dn + dv)),
                          "wo": dense(k[3], h * dv, d),
                          "wg": dense(k[4], d, h)}
        if ffn == "glu":
            g = lm.glu_width
            blk["glu"] = {"w1": dense(k[12], d, g), "w3": dense(k[13], d, g),
                          "w2": dense(k[14], g, d)}
        else:
            e, n, f = lm.num_experts, lm.experts_held, lm.d_ff
            w = lm.moe["shared_width"]
            blk["moe"] = {
                "router": dense(k[15], d, e),
                "bias": normal(k[16], (e,), 0.01),
                "w_gate": glorot(k[17], (n, d, f), d, f),
                "w_up": glorot(k[18], (n, d, f), d, f),
                "w_down": glorot(k[19], (n, f, d), f, d),
                "shared": {"w_gate": dense(k[20], d, w),
                           "w_up": dense(k[21], d, w),
                           "w_down": dense(k[22], w, d)}}
        return blk

    return jax.jit(init)


def make_params(lm, seed: int):
    """Weights on the device from ``seed``: one jitted call a block (one
    compile a kind of block) and one for the embedding and the head.
    ``init()`` itself is never called: its Adam moments would not fit."""
    v, d, dt = lm.vocab_size, lm.d_model, lm.policy.param_dtype
    inits = {kind: _block_init(lm, *kind)
             for kind in set(zip(lm.mixers, lm.ffns))}

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": jax.random.normal(k[0], (v, d), dt) * 0.02,
                "head": jax.random.normal(k[1], (v, d), dt) * 0.02,
                "ln_f": {"g": jnp.ones((d,), dt)}}

    keys = jax.random.split(jax.random.PRNGKey(seed), lm.num_layers + 1)
    _check_tree(lm, {
        **jax.eval_shape(ends, keys[0]),
        "blocks": [jax.eval_shape(inits[kind], keys[0])
                   for kind in zip(lm.mixers, lm.ffns)]})
    params = ends(keys[0])
    params["blocks"] = [inits[kind](keys[1 + i]) for i, kind in
                        enumerate(zip(lm.mixers, lm.ffns))]
    return params


def build_model(ctx):
    sv = ctx.cell["server"]
    lm = build_lm(ctx.config, policy=sv["policy"], seed=ctx.seed,
                  max_len=int(sv["max_len"]))
    lm.params = make_params(lm, ctx.seed)
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


# ---- the window --------------------------------------------------------------
def serve_window(ctx, lm):
    """``lm_serve_moe.serve_window`` with this model's counters: what the
    server itself counted over the window, by difference of two
    ``stats()``. The server is local to this function: when it returns
    nothing holds the pool any more."""
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
    steps1, slot1 = marks.get("on", now)
    steps = after["steps"] - before["steps"]
    load = (np.asarray(after["moe_expert_load"])
            - np.asarray(before["moe_expert_load"]))     # [Lmoe, held]
    rows = after["moe_rows"] - before["moe_rows"]
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
        "moe_load_max_over_mean": float(
            (load.max(axis=1) / np.maximum(load.mean(axis=1), 1e-9)).max()),
        "moe_experts_touched_per_step": (float(np.mean(touched))
                                         if touched else 0.0),
        "moe_live_slots_per_step": (now[1] - slot0) / max(1, steps),
        # pairs on an expert held here per live row and expert layer
        "routed_pairs_here_per_token": float(load.sum()) / max(
            1, rows * load.shape[0]),
        **{"state_bytes_" + kind: n
           for kind, n in after["state_bytes"].items()},
    }
    if "on" in marks:
        counters["decode_steps_in_trace"] = (marks.get("done", now)[0]
                                             - marks["on"][0])
    return res, counters, rng


def check_against_reference(lm, cfg, finished, check, traffic, rng,
                            forward_tail=None):
    """Routing and tokens of a seeded sample of finished requests, as the
    window's programs computed them, against the plain reference; see the
    module's docstring. ``cfg`` is ``reference_config``'s."""
    forward_tail = forward_tail or reference_ling.forward_tail
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
        # [Lmoe, T, k]: the prompt's rows from the prefill program, then
        # one row from each decode step that emitted a token but the last
        experts, weights = (np.concatenate(x, axis=1)
                            for x in zip(*o.request.routing))
        if experts.shape[1] != len(seq):
            raise RuntimeError(f"request {o.request.id}: {experts.shape[1]} "
                               f"rows of routing for {len(seq)} positions")
        logits, routes = forward_tail(lm.params, seq, cfg, n_tail,
                                      pad_to=pad_to, chosen=experts)
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
                 f"({flipped} of {pairs} served (token, layer) pairs chose a "
                 f"group or an expert the reference would not; allowed up "
                 f"to a shortfall of route_gap={check['route_gap']})")
    return ok, notes


def run(ctx) -> Outcome:
    cfg, cell = ctx.config, ctx.cell
    if cell["loop"]["cut_at_seconds"]:
        raise SystemExit("lm_serve_hybrid drains: no cell of it cuts its "
                         "window")
    lm = build_model(ctx)
    res, counters, rng = serve_window(ctx, lm)
    gc.collect()

    times = [lm_serve.request_times(res, o) for o in res.offered]
    finished = [o for o, x in zip(res.offered, times) if x[2]]
    refused = sum(1 for o in res.offered if o.request is None)
    failed = len(res.offered) - len(finished)
    ttft = [x[0] for o, x in zip(res.offered, times)
            if x[0] == INF or o.request.first_token_s <= ctx.trace_t0]
    tpot = [x[1] for o, x in zip(res.offered, times)
            if x[1] is not None and o.request.finish_s <= ctx.trace_t0]
    tpot += [INF] * failed
    late = [o.late_s for o in res.offered
            if res.t0 + o.arrival.due_s <= ctx.trace_t0]
    done_tokens = sum(len(o.request.tokens) for o in finished)
    # the mean number of cached positions a decode step's live slot attends:
    # a finished request's j-th decode step sits at prompt_len + j
    ctx_sum = sum(len(o.request.tokens) * len(o.arrival.prompt)
                  + len(o.request.tokens) * (len(o.request.tokens) - 1) // 2
                  for o in finished)
    counters.update({
        "requests_offered": len(res.offered),
        "requests_finished": len(finished),
        "tokens_finished": done_tokens,
        "decode_context_mean": ctx_sum / max(1, done_tokens),
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
             f"hybrid: routed_pairs={counters['moe_routed_pairs']} "
             f"pairs_here_per_token="
             f"{counters['routed_pairs_here_per_token']:.4f} "
             f"load_max_over_mean={counters['moe_load_max_over_mean']:.4f} "
             f"experts_touched_per_step="
             f"{counters['moe_experts_touched_per_step']:.2f} "
             f"live_slots_per_step="
             f"{counters['moe_live_slots_per_step']:.2f} "
             f"decode_context_mean={counters['decode_context_mean']:.1f} "
             + " ".join(f"{k}={v}" for k, v in sorted(counters.items())
                        if k.startswith("state_bytes_"))]

    # ---- correct: the pool is gone, so the reference has room
    ok = (counters["program_builds_in_window"] == 0 and bool(finished)
          and failed == 0)
    if finished:
        ref_ok, ref_notes = check_against_reference(
            lm, reference_config(cfg), finished, cell["check"],
            cell["traffic"], rng)
        ok &= ref_ok
        notes += ref_notes
    return Outcome(
        correct=ok, attempted=len(res.offered), failed=failed,
        end_to_end={"serve_tpot_p50_ms": counters["tpot_p50_ms"]},
        counters=counters, notes=notes)
