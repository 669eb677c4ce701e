"""Driver ``lm_serve_retention``: ``lm_serve``'s open loop for a dense model
whose every layer is power retention (``brumby-14b-l4``: no layer keeps rows
a position; a slot's whole cache is a float32 state a layer), on one chip.

The window (server, warm-up, schedule, clock, drain) is
``lm_serve.serve_window`` by import: this model routes nothing, so the
routing-recording window of ``lm_serve_hybrid`` (which ISSUE 47 named) does
not apply: ``DecodeServer(record_routing=True)`` refuses a model without
experts. This driver brings what the model changes:

- its builder: ``TransformerLM`` from the configuration file (``mixers``
  all ``ret``, a dense SwiGLU), and its weights, made on the device from the
  seed one block at a time, with the gate seeding the configuration file
  states under ``assumed.gate_seeding``;
- the program's own counts of the window, from its ``serve.decode`` spans
  (the host's cursors): slots whose state a step moved (``state_slots``) and
  the rows a position it read (``kv_rows``: 0), and the pool's bytes by kind
  (``kv_cache.pool_layout``, the description the server builds it from);
- the check's own tail of judged positions (``lm_serve``'s stops at 256
  generated tokens): **every** generated token of ``check.sample_short``
  seeded finished requests and the one with the longest prompt, among those
  with prompts of at most ``check.longest_max_prompt`` tokens (the
  reference's quadratic form over a longer one would not fit beside the
  weights), each the argmax of ``lib/reference_brumby.forward_tail``'s
  float32 teacher-forced logits over the vocabulary slice or within
  ``check.near_tie`` x max|logit| of it.

Workload file keys: those of ``lm_serve`` and ``traffic.limits``,
``check.{longest_max_prompt, pad_to}``.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import lm_serve
from benchmarks.drivers._moe_common import _check_tree
from benchmarks.lib import loadgen, reference_brumby
from benchmarks.lib.outcome import Outcome

INF = float("inf")
# the seeded gate (the configuration file's ``assumed.gate_seeding``)
GATE_BIAS = (2.5, 7.0)


# ---- the model from its configuration file ----------------------------------
def build_lm(config: dict, *, policy: str, seed: int, max_len: int):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    layers = config["num_hidden_layers"]
    if len(config["kept_layers"]) != layers:
        raise SystemExit("kept_layers and num_hidden_layers disagree")
    if config["hidden_size"] != (config["num_attention_heads"]
                                 * config["head_dim"]):
        raise SystemExit("the program's power retention takes heads of "
                         "hidden_size / num_attention_heads")
    if config["sliding_window"] or config["rope_scaling"]:
        raise SystemExit("no layer of this model has a window or a scaled "
                         "RoPE")
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], num_layers=layers,
        max_len=max_len, seed=seed, dtype_policy=policy, pos_encoding="rope",
        norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"],
        mixers=["ret"] * layers, ffns=["glu"] * layers,
        glu_width=config["intermediate_size"],
        ret={"power": config["power"]})


def reference_config(config: dict) -> dict:
    """What ``lib/reference_brumby.py`` reads, from the configuration file."""
    return {k: config[k] for k in reference_brumby.KEYS}


def _block_init(lm):
    """``key -> block``: Glorot-normal matrices, unit gains, ``b_g`` =
    linspace(``GATE_BIAS``) over the key/value heads."""
    d, dt = lm.d_model, lm.policy.param_dtype
    h, hkv, dh, g = lm.num_heads, lm.num_kv_heads, lm.head_dim, lm.glu_width

    def dense(key, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dt)
        return jax.random.normal(key, (fan_in, fan_out), dt) * scale

    def gain(width=d):
        return {"g": jnp.ones((width,), dt)}

    def init(key):
        k = jax.random.split(key, 8)
        return {"ln1": gain(), "ln2": gain(),
                "ret": {"wq": dense(k[0], d, h * dh),
                        "wk": dense(k[1], d, hkv * dh),
                        "wv": dense(k[2], d, hkv * dh),
                        "wo": dense(k[3], h * dh, d),
                        "wg": dense(k[4], d, hkv),
                        "bg": jnp.linspace(*GATE_BIAS, hkv).astype(dt),
                        "q_norm": gain(dh), "k_norm": gain(dh)},
                "glu": {"w1": dense(k[5], d, g), "w3": dense(k[6], d, g),
                        "w2": dense(k[7], g, d)}}

    return jax.jit(init)


def make_params(lm, seed: int):
    """Weights on the device from ``seed``: one jitted call a block (one
    compile) and one for the embedding and the head. ``init()`` itself is
    never called: its Adam moments would not fit."""
    v, d, dt = lm.vocab_size, lm.d_model, lm.policy.param_dtype
    block = _block_init(lm)

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": jax.random.normal(k[0], (v, d), dt) * 0.02,
                "head": jax.random.normal(k[1], (v, d), dt) * 0.02,
                "ln_f": {"g": jnp.ones((d,), dt)}}

    keys = jax.random.split(jax.random.PRNGKey(seed), lm.num_layers + 1)
    _check_tree(lm, {**jax.eval_shape(ends, keys[0]),
                     "blocks": [jax.eval_shape(block, keys[0])]
                     * lm.num_layers})
    params = ends(keys[0])
    params["blocks"] = [block(keys[1 + i]) for i in range(lm.num_layers)]
    return params


def build_model(ctx):
    sv = ctx.cell["server"]
    lm = build_lm(ctx.config, policy=sv["policy"], seed=ctx.seed,
                  max_len=int(sv["max_len"]))
    lm.params = make_params(lm, ctx.seed)
    return lm


build_server = lm_serve.build_server        # what the knee tools call


# ---- the window --------------------------------------------------------------
def serve_window(ctx, lm):
    """``lm_serve.serve_window`` (drained) with what this model's readers
    divide by beside its counters: ``state_slots`` and ``kv_rows`` of the
    window's own ``serve.decode`` spans that dispatched (a program without
    the attrs, the parent's, gives none), and the pool's bytes by kind."""
    from deeplearning4j_tpu.monitor import trace as program_trace
    from deeplearning4j_tpu.serving import kv_cache

    seen = {"kv_rows": 0, "state_slots": 0, "steps": 0}

    def sink(span):
        if ctx.t_window is None or ctx.t_window_end is not None:
            return
        attrs = span["attrs"]
        if span["name"] == "serve.decode" and "state_slots" in attrs:
            seen["kv_rows"] += attrs.get("kv_rows", 0)
            seen["state_slots"] += attrs["state_slots"]
            seen["steps"] += 1

    program_trace.add_sink(sink)
    try:
        res, counters, rng = lm_serve.serve_window(ctx, lm, False)
    finally:
        program_trace.remove_sink(sink)
    if seen["steps"]:
        counters["kv_rows_per_step"] = seen["kv_rows"] / seen["steps"]
        counters["state_slots_per_step"] = (seen["state_slots"]
                                            / seen["steps"])
    sv = ctx.cell["server"]
    layout = kv_cache.pool_layout(
        lm, int(sv["slots"]), int(sv["max_len"]),
        kv_cache.resolve_kv_dtype(None, lm))
    counters.update({"state_bytes_" + kind: kv_cache._layout_nbytes(arrays)
                     for kind, arrays in layout.items()})
    return res, counters, rng


def check_against_reference(lm, cfg, finished, check, traffic, rng):
    """Every generated token of a seeded sample of finished requests against
    the plain reference; see the module's docstring. ``cfg`` is
    ``reference_config``'s."""
    limit = int(check["longest_max_prompt"])
    judged = [o for o in finished if len(o.arrival.prompt) <= limit]
    notes = []
    if len(judged) < len(finished):
        notes.append(f"check: {len(finished) - len(judged)} finished requests "
                     f"with prompts over {limit} tokens are not sampled")
    if not judged:
        return False, notes + ["check: no finished request to judge"]
    short = [o for o in judged
             if len(o.arrival.prompt) <= check["short_max_prompt"]]
    picks = [short[j] for j in rng.permutation(len(short))
             [:check["sample_short"]]]
    longest = max(judged, key=lambda o: len(o.arrival.prompt))
    if longest not in picks:
        picks.append(longest)
    # one length for every sequence, one tail for every answer: the
    # reference compiles once
    pad_to = int(check["pad_to"])
    n_tail = int(traffic["output_tokens"]["max"])
    ok = True
    for o in picks:
        toks = np.asarray(o.request.tokens, np.int32)
        seq = np.concatenate([o.arrival.prompt, toks])[:-1]
        n = len(toks)
        logits = np.asarray(reference_brumby.forward_tail(
            lm.params, seq, cfg, n_tail, pad_to=pad_to))[-n:]
        best = logits.max(axis=-1)
        gap = (best - logits[np.arange(n), toks]) / np.abs(logits).max(-1)
        bad = int(np.sum(gap > check["near_tie"]))
        ok &= bad == 0
        notes.append(f"check: prompt={len(o.arrival.prompt)} new={n} "
                     f"judged={n} off_argmax={int(np.sum(gap > 0))} "
                     f"worst_gap={float(gap.max()):.5f} "
                     f"beyond_near_tie={bad}")
    return bool(ok), notes


def run(ctx) -> Outcome:
    cfg, cell = ctx.config, ctx.cell
    if cell["loop"]["cut_at_seconds"]:
        raise SystemExit("lm_serve_retention drains: no cell of it cuts its "
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
    if not tpot:    # a traced run in which nothing finished before the trace
        tpot = [x[1] for x in times if x[1] is not None]
    tpot += [INF] * failed
    late = [o.late_s for o in res.offered
            if res.t0 + o.arrival.due_s <= ctx.trace_t0]
    done_tokens = sum(len(o.request.tokens) for o in finished)
    # the limits of this cell (``traffic.limits``): TTFT grows with the prompt
    lim = cell["traffic"].get("limits")
    within = [x[0] <= lim["ttft_s"] + lim["ttft_s_per_1k_prompt"]
              * len(o.arrival.prompt) / 1024
              and (x[1] is None or x[1] <= lim["tpot_s"])
              for o, x in zip(res.offered, times)] if lim else []
    counters.update({
        "requests_offered": len(res.offered),
        "requests_finished": len(finished),
        "tokens_finished": done_tokens,
        "tokens_per_s": done_tokens / max(res.window_s, 1e-9),
        "attainment_pct": 100.0 * sum(within) / max(1, len(within)),
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
             f"attainment_pct={counters['attainment_pct']:.2f} "
             f"queue_at_end={counters['queue_depth_at_end']}",
             f"retention: state_slots_per_step="
             f"{counters.get('state_slots_per_step', 0):.2f} "
             f"kv_rows_per_step={counters.get('kv_rows_per_step', 0):.0f} "
             f"slot_occupancy_pct={counters['slot_occupancy_pct']:.2f} "
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
