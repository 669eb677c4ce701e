"""Driver ``lm_serve_swa``: ``lm_serve``'s open loop for a model of
sliding-window attention layers beside full-attention layers
(``mellum2-12b-a2.5b-l4``: three window layers and one full layer, a RoPE a
layer kind, 8 of 64 softmax-routed experts in every layer), on one chip.

The window (warm-up, schedule, clock, the server that records its routing) is
``lm_serve_hybrid.serve_window`` and the routing-and-token check
``lm_serve_hybrid.check_against_reference``, both by import: that check takes
the reference as an argument. This driver brings what the model changes:

- its builder: ``TransformerLM`` from the configuration file (a window a
  layer from ``layer_types`` and ``sliding_window``, a RoPE a layer kind from
  ``rope_parameters``), and its weights, made on the device from the seed one
  block at a time;
- the program's own counts of the window, from its ``serve.decode`` spans
  (the host's cursors): K/V rows the live slots held a decode step in the
  window layers' rings and in the full layers' pool (``kv_rows_window``,
  ``kv_rows_full``), what the ``win_attn_*`` and ``full_attn_*`` readers
  divide by;
- the reference (``lib/reference_mellum2.py``), handed the experts the
  window's own prefill and decode programs chose, over a seeded sample of the
  finished requests with a prompt of at most ``check.longest_max_prompt``
  tokens (the reference's attention over a longer one would not fit beside
  the weights), chosen so that the ring is seen at work: ``check.sample``
  requests and the longest such, at least ``check.min_over_window`` of them
  with a prompt longer than the window and one whose cursor crosses a
  multiple of the ring's length while it decodes, where the window finished
  any.

Workload file keys: those of ``lm_serve_moe`` and ``traffic.limits``,
``check.{sample, min_over_window, longest_max_prompt, pad_to}``.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp

from benchmarks.drivers import lm_serve, lm_serve_hybrid
from benchmarks.drivers._moe_common import _check_tree
from benchmarks.lib import loadgen, reference_mellum2
from benchmarks.lib.outcome import Outcome

INF = float("inf")
SCALING_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow")


# ---- the model from its configuration file ----------------------------------
def rope_by_kind(config: dict) -> dict:
    """``TransformerLM(attn={"rope": ...})`` from ``rope_parameters``: the
    sliding layers' section is the program's ``window`` kind."""
    out = {}
    for kind, section in (("window", "sliding_attention"),
                          ("full", "full_attention")):
        rope = config["rope_parameters"][section]
        out[kind] = {"theta": rope["rope_theta"]}
        if rope["rope_type"] == "yarn":
            out[kind]["scaling"] = {k: rope[k] for k in SCALING_KEYS}
        elif rope["rope_type"] != "default":
            raise SystemExit(f"rope_type {rope['rope_type']!r} is not written")
    return out


def build_lm(config: dict, *, policy: str, seed: int, max_len: int):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    if not hasattr(TransformerLM, "by_layer"):
        raise SystemExit(
            "this program's TransformerLM has one window and one RoPE a "
            "model: it cannot run a configuration that gives them by layer "
            "(attn={'windows': ..., 'rope': ...}, PR 44)")
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or len(kinds) != len(
            config["kept_layers"]):
        raise SystemExit("layer_types, kept_layers and num_hidden_layers "
                         "disagree")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise SystemExit("every layer kept is an expert layer")
    if not config["use_sliding_window"] or config["attention_bias"]:
        raise SystemExit("the program's layers have the window and no bias")
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], num_layers=len(kinds),
        d_ff=config["moe_intermediate_size"], max_len=max_len, seed=seed,
        dtype_policy=policy, pos_encoding="rope", norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        mixers=["attn"] * len(kinds), ffns=["moe"] * len(kinds),
        attn={"head_dim": config["head_dim"], "head_norm": True,
              "windows": [config["sliding_window"]
                          if k == "sliding_attention" else None
                          for k in kinds],
              "rope": rope_by_kind(config)})


def reference_config(config: dict) -> dict:
    """What ``lib/reference_mellum2.py`` reads, from the configuration
    file."""
    return {k: config[k] for k in reference_mellum2.KEYS}


def _block_init(lm):
    """``key -> block``: Glorot-normal matrices, unit gains."""
    d, dt = lm.d_model, lm.policy.param_dtype

    def glorot(key, shape, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dt)
        return jax.random.normal(key, shape, dt) * scale

    def gain(width=d):
        return {"g": jnp.ones((width,), dt)}

    def init(key):
        k = jax.random.split(key, 8)
        h, hkv, dh = lm.num_heads, lm.num_kv_heads, lm.head_dim
        e, f = lm.num_experts, lm.d_ff
        return {
            "ln1": gain(), "ln2": gain(),
            "attn": {"wq": glorot(k[0], (d, h * dh), d, h * dh),
                     "wk": glorot(k[1], (d, hkv * dh), d, hkv * dh),
                     "wv": glorot(k[2], (d, hkv * dh), d, hkv * dh),
                     "wo": glorot(k[3], (h * dh, d), h * dh, d),
                     "q_norm": gain(dh), "k_norm": gain(dh)},
            "moe": {"router": glorot(k[4], (d, e), d, e),
                    "w_gate": glorot(k[5], (e, d, f), d, f),
                    "w_up": glorot(k[6], (e, d, f), d, f),
                    "w_down": glorot(k[7], (e, f, d), f, d)}}

    return jax.jit(init)


def make_params(lm, seed: int):
    """Weights on the device from ``seed``: one jitted call a block (one
    compile) and one for the embedding and the head. ``init()`` itself is
    never called: its Adam moments would not fit."""
    v, d, dt = lm.vocab_size, lm.d_model, lm.policy.param_dtype
    init = _block_init(lm)

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": jax.random.normal(k[0], (v, d), dt) * 0.02,
                "head": jax.random.normal(k[1], (v, d), dt) * 0.02,
                "ln_f": {"g": jnp.ones((d,), dt)}}

    keys = jax.random.split(jax.random.PRNGKey(seed), lm.num_layers + 1)
    _check_tree(lm, {**jax.eval_shape(ends, keys[0]),
                     "blocks": [jax.eval_shape(init, keys[0])]
                     * lm.num_layers})
    params = ends(keys[0])
    params["blocks"] = [init(keys[1 + i]) for i in range(lm.num_layers)]
    return params


def build_model(ctx):
    sv = ctx.cell["server"]
    lm = build_lm(ctx.config, policy=sv["policy"], seed=ctx.seed,
                  max_len=int(sv["max_len"]))
    lm.params = make_params(lm, ctx.seed)
    return lm


# ---- the window --------------------------------------------------------------
build_server = lm_serve_hybrid.build_server     # what the knee tools call


def serve_window(ctx, lm):
    """``lm_serve_hybrid.serve_window`` with what this model's readers divide
    by beside its counters: ``kv_rows_window`` and ``kv_rows_full`` of the
    window's own ``serve.decode`` spans that dispatched (nothing of the
    warm-up: the window has not begun then; a program without the attrs
    gives none)."""
    from deeplearning4j_tpu.monitor import trace as program_trace

    seen = {"kv_rows_window": 0, "kv_rows_full": 0, "steps": 0}

    def sink(span):
        if ctx.t_window is None or ctx.t_window_end is not None:
            return
        attrs = span["attrs"]
        if span["name"] == "serve.decode" and "kv_rows_full" in attrs:
            seen["kv_rows_window"] += attrs["kv_rows_window"]
            seen["kv_rows_full"] += attrs["kv_rows_full"]
            seen["steps"] += 1

    program_trace.add_sink(sink)
    try:
        res, counters, rng = lm_serve_hybrid.serve_window(ctx, lm)
    finally:
        program_trace.remove_sink(sink)
    if seen["steps"]:
        for kind in ("window", "full"):
            counters[f"kv_rows_{kind}_per_step"] = (
                seen[f"kv_rows_{kind}"] / seen["steps"])
    return res, counters, rng


def pick_judged(finished, check, window: int, rng):
    """The finished requests the reference judges (the module's docstring)
    and a note that says which conditions the sample met."""
    limit = int(check["longest_max_prompt"])
    judged = [o for o in finished if len(o.arrival.prompt) <= limit]
    if not judged:
        return [], "check: no finished request to judge"
    order = [judged[j] for j in rng.permutation(len(judged))]

    def laps(o):    # a cursor of its decode steps is a multiple of the ring
        p, n = len(o.arrival.prompt), len(o.request.tokens)
        return (p + n - 2) // window > (p - 1) // window

    def over(o):
        return len(o.arrival.prompt) > window

    picks = [max(judged, key=lambda o: len(o.arrival.prompt))]
    wanted = int(check["sample"]) + 1
    for need in (lambda o: laps(o) and over(o), laps):
        if not any(map(laps, picks)):
            picks += [o for o in order if need(o) and o not in picks][:1]
    while sum(map(over, picks)) < int(check["min_over_window"]):
        more = [o for o in order if over(o) and o not in picks][:1]
        if not more:
            break
        picks += more
    picks += [o for o in order if o not in picks][:max(0, wanted - len(picks))]
    return picks, (
        f"check: {len(picks)} of {len(judged)} finished requests with "
        f"prompts <= {limit} judged ({len(finished) - len(judged)} longer "
        f"ones are not sampled): {sum(map(over, picks))} with a prompt over "
        f"the window of {window}, {sum(map(laps, picks))} whose cursor "
        f"crosses a multiple of the ring while decoding")


def check_against_reference(lm, config, finished, check, traffic, rng):
    """``lm_serve_hybrid.check_against_reference`` with this model's
    reference over ``pick_judged``'s sample."""
    picks, note = pick_judged(finished, check, int(config["sliding_window"]),
                              rng)
    if not picks:
        return False, [note]
    ok, more = lm_serve_hybrid.check_against_reference(
        lm, reference_config(config), picks,
        {**check, "short_max_prompt": int(check["longest_max_prompt"]),
         "sample_short": len(picks)},
        {"max_total_tokens": int(check["pad_to"]),
         "output_tokens": traffic["output_tokens"]}, rng,
        forward_tail=reference_mellum2.forward_tail)
    return bool(ok), [note] + more


def run(ctx) -> Outcome:
    cfg, cell = ctx.config, ctx.cell
    if cell["loop"]["cut_at_seconds"]:
        raise SystemExit("lm_serve_swa drains: no cell of it cuts its window")
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
             f"swa: kv_rows_window_per_step="
             f"{counters.get('kv_rows_window_per_step', 0):.0f} "
             f"kv_rows_full_per_step="
             f"{counters.get('kv_rows_full_per_step', 0):.0f} "
             f"routed_pairs={counters['moe_routed_pairs']} "
             f"load_max_over_mean={counters['moe_load_max_over_mean']:.4f} "
             f"experts_touched_per_step="
             f"{counters['moe_experts_touched_per_step']:.2f} "
             f"live_slots_per_step="
             f"{counters['moe_live_slots_per_step']:.2f} "
             + " ".join(f"{k}={v}" for k, v in sorted(counters.items())
                        if k.startswith("state_bytes_"))]

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
