"""Driver ``lm_serve_gdn``: ``lm_serve``'s open loop for a model of Gated
DeltaNet layers beside gated softmax attention (``qwen3-next-80b-a3b-l4``:
three recurrent layers and one attention layer over a K/V pool, softmax
routing with a gated shared expert, one chip's share of the experts and of
the vocabulary), on one chip.

The window (warm-up, schedule, clock, the server that records its routing) is
``lm_serve_hybrid.serve_window`` and the routing-and-token check
``lm_serve_hybrid.check_against_reference``, both by import: that check takes
the reference as an argument. This driver brings what the model changes:

- its builder: ``TransformerLM`` from the configuration file (which of the
  kept layers are attention, the experts held of the router's), and its
  weights, made on the device from the seed one block at a time, with the
  gate seeding the configuration file states under ``assumed``;
- the program's own counts of the window, from its ``serve.decode`` spans
  (the host's cursors): K/V rows the live slots held a decode step
  (``kv_rows``) and slots whose recurrent state a step moved
  (``state_slots``), what the ``pool_attn_*`` and ``gdn_*`` readers divide
  by;
- the reference (``lib/reference_qwen3_next.py``), handed the experts the
  window's own prefill and decode programs chose; of the finished requests
  those with a prompt of at most ``check.longest_max_prompt`` tokens are
  sampled (the reference's attention over a longer one would not fit beside
  the weights).

Workload file keys: those of ``lm_serve_moe`` and ``traffic.limits``,
``check.{longest_max_prompt, pad_to}``.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp

from benchmarks.drivers import lm_serve, lm_serve_hybrid
from benchmarks.drivers._moe_common import _check_tree
from benchmarks.lib import loadgen, reference_qwen3_next
from benchmarks.lib.outcome import Outcome

INF = float("inf")
# the seeded gate (the configuration file's ``assumed.gate_seeding``)
A_RANGE, DT_BIAS = (1.0, 16.0), -4.0


# ---- the model from its configuration file ----------------------------------
def layer_kinds(config: dict):
    """The mixers of the layers kept: a published layer whose index + 1 is a
    multiple of ``full_attention_interval`` is attention, the others Gated
    DeltaNet."""
    period = config["full_attention_interval"]
    return ["attn" if (i + 1) % period == 0 else "gdn"
            for i in config["kept_layers"]]


def build_lm(config: dict, *, policy: str, seed: int, max_len: int):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    mixers = layer_kinds(config)
    if len(mixers) != config["num_hidden_layers"]:
        raise SystemExit("kept_layers and num_hidden_layers disagree")
    if config["linear_key_head_dim"] != config["linear_value_head_dim"]:
        raise SystemExit("the program's Gated DeltaNet has one head size")
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise SystemExit("every layer kept is an expert layer")
    share = config["share"]
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], num_layers=len(mixers),
        d_ff=config["moe_intermediate_size"], max_len=max_len, seed=seed,
        dtype_policy=policy, pos_encoding="rope", norm="rmsnorm",
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"],
        num_experts=config["published"]["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        mixers=mixers, ffns=["moe"] * len(mixers),
        gdn={"key_heads": config["linear_num_key_heads"],
             "value_heads": config["linear_num_value_heads"],
             "head_dim": config["linear_key_head_dim"],
             "conv": config["linear_conv_kernel_dim"]},
        attn={"head_dim": config["head_dim"],
              "rotary_dim": config["rotary_dim"], "head_norm": True,
              "gate": True},
        moe={"shared_width": config["shared_expert_intermediate_size"],
             "shared_gate": True, "first": share["first_expert"],
             "held": config["num_experts"]})


def reference_config(config: dict) -> dict:
    """What ``lib/reference_qwen3_next.py`` reads, from the configuration
    file."""
    return {**{k: config[k] for k in reference_qwen3_next.KEYS},
            "share": {"first_expert": config["share"]["first_expert"],
                      "held": config["num_experts"]}}


def _block_init(lm, mixer: str):
    """``key -> block`` for one kind of layer: Glorot-normal matrices, unit
    gains, convolution taps normal / sqrt(taps), ``A_log`` = log U(1, 16)
    and ``dt_bias`` = -4 (``A_RANGE``, ``DT_BIAS``)."""
    d, dt = lm.d_model, lm.policy.param_dtype

    def glorot(key, shape, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dt)
        return jax.random.normal(key, shape, dt) * scale

    def dense(key, fan_in, fan_out):
        return glorot(key, (fan_in, fan_out), fan_in, fan_out)

    def gain(width=d):
        return {"g": jnp.ones((width,), dt)}

    def init(key):
        k = jax.random.split(key, 16)
        blk = {"ln1": gain(), "ln2": gain()}
        if mixer == "gdn":
            g = lm.gdn
            hv, dk, taps = g["value_heads"], g["head_dim"], g["conv"]
            ck, cv = g["key_heads"] * dk, hv * dk
            blk["gdn"] = {
                "w_qkvz": dense(k[0], d, 2 * ck + 2 * cv),
                "w_ba": dense(k[1], d, 2 * hv),
                "wo": dense(k[2], cv, d),
                "conv": jax.random.normal(k[3], (taps, 2 * ck + cv), dt)
                * taps ** -0.5,
                "a_log": jnp.log(jax.random.uniform(
                    k[4], (hv,), dt, *A_RANGE)),
                "dt_bias": jnp.full((hv,), DT_BIAS, dt),
                "o_norm": gain(dk)}
        else:
            h, hkv, dh = lm.num_heads, lm.num_kv_heads, lm.head_dim
            blk["attn"] = {"wq": dense(k[0], d, 2 * h * dh),
                           "wk": dense(k[1], d, hkv * dh),
                           "wv": dense(k[2], d, hkv * dh),
                           "wo": dense(k[3], h * dh, d),
                           "q_norm": gain(dh), "k_norm": gain(dh)}
        e, n, f = lm.num_experts, lm.experts_held, lm.d_ff
        w = lm.moe["shared_width"]
        blk["moe"] = {
            "router": dense(k[8], d, e),
            "w_gate": glorot(k[9], (n, d, f), d, f),
            "w_up": glorot(k[10], (n, d, f), d, f),
            "w_down": glorot(k[11], (n, f, d), f, d),
            "shared": {"w_gate": dense(k[12], d, w),
                       "w_up": dense(k[13], d, w),
                       "w_down": dense(k[14], w, d),
                       "gate": glorot(k[15], (d,), d, 1)}}
        return blk

    return jax.jit(init)


def make_params(lm, seed: int):
    """Weights on the device from ``seed``: one jitted call a block (one
    compile a kind of block) and one for the embedding and the head.
    ``init()`` itself is never called: its Adam moments would not fit."""
    v, d, dt = lm.vocab_size, lm.d_model, lm.policy.param_dtype
    inits = {kind: _block_init(lm, kind) for kind in set(lm.mixers)}

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
                   for kind in lm.mixers]})
    params = ends(keys[0])
    params["blocks"] = [inits[kind](keys[1 + i])
                        for i, kind in enumerate(lm.mixers)]
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
    by beside its counters: ``kv_rows`` and ``state_slots`` of the window's
    own ``serve.decode`` spans that dispatched (nothing of the warm-up: the
    window has not begun then; a program without the attrs, the parent's,
    gives none)."""
    from deeplearning4j_tpu.monitor import trace as program_trace

    seen = {"kv_rows": 0, "state_slots": 0, "steps": 0}

    def sink(span):
        if ctx.t_window is None or ctx.t_window_end is not None:
            return
        attrs = span["attrs"]
        if span["name"] == "serve.decode" and "kv_rows" in attrs:
            seen["kv_rows"] += attrs["kv_rows"]
            seen["state_slots"] += attrs.get("state_slots", 0)
            seen["steps"] += 1

    program_trace.add_sink(sink)
    try:
        res, counters, rng = lm_serve_hybrid.serve_window(ctx, lm)
    finally:
        program_trace.remove_sink(sink)
    if seen["steps"]:
        counters["kv_rows_per_step"] = seen["kv_rows"] / seen["steps"]
        counters["state_slots_per_step"] = (seen["state_slots"]
                                            / seen["steps"])
    return res, counters, rng


def check_against_reference(lm, cfg, finished, check, traffic, rng):
    """``lm_serve_hybrid.check_against_reference`` with this model's
    reference over the finished requests whose prompt the reference can
    hold; see the module's docstring."""
    limit = int(check["longest_max_prompt"])
    judged = [o for o in finished if len(o.arrival.prompt) <= limit]
    notes = []
    if len(judged) < len(finished):
        notes.append(f"check: {len(finished) - len(judged)} finished requests "
                     f"with prompts over {limit} tokens are not sampled")
    if not judged:
        return False, notes + ["check: no finished request to judge"]
    ok, more = lm_serve_hybrid.check_against_reference(
        lm, cfg, judged, check,
        {"max_total_tokens": int(check["pad_to"]),
         "output_tokens": traffic["output_tokens"]}, rng,
        forward_tail=reference_qwen3_next.forward_tail)
    return bool(ok), notes + more


def run(ctx) -> Outcome:
    cfg, cell = ctx.config, ctx.cell
    if cell["loop"]["cut_at_seconds"]:
        raise SystemExit("lm_serve_gdn drains: no cell of it cuts its window")
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
             f"gdn: kv_rows_per_step="
             f"{counters.get('kv_rows_per_step', 0):.0f} "
             f"state_slots_per_step="
             f"{counters.get('state_slots_per_step', 0):.2f} "
             f"routed_pairs={counters['moe_routed_pairs']} "
             f"pairs_here_per_token="
             f"{counters['routed_pairs_here_per_token']:.4f} "
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
            lm, reference_config(cfg), finished, cell["check"],
            cell["traffic"], rng)
        ok &= ref_ok
        notes += ref_notes
    return Outcome(
        correct=ok, attempted=len(res.offered), failed=failed,
        end_to_end={"serve_tpot_p50_ms": counters["tpot_p50_ms"]},
        counters=counters, notes=notes)
