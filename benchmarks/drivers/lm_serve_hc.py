"""Driver ``lm_serve_hc``: ``lm_serve``'s open loop for GLM-5.3-Flash's
language model (``glm-5.3-flash-l5``: a four-stream residual mixed by
manifold-constrained hyper-connections round every sub-layer, KDA layers
three to one with a latent layer without a rotary part whose indexer scores
pooled keys, sigmoid routing with a shared expert under ``swiglu_limit``, one
chip's share of the experts and of the vocabulary), on one chip.

The window (warm-up, schedule, clock, the server that records its routing and
its selections, the sparse-attention counts of its spans) is
``lm_serve_dsa.serve_window`` and the routing-and-token check
``lm_serve_hybrid.check_against_reference``, both by import. This driver
brings what the model changes:

- its builder: ``TransformerLM`` from the configuration file (the kept
  layers' ``layer_types`` and ``mlp_layer_types``, ``hc=``, ``kda=`` with the
  low-rank gates, ``mla=`` with ``qk_rope_head_dim`` 0, ``dsa=`` with
  ``pool``), and its weights, made on the device from the seed one block at a
  time, with the scales the configuration file states under ``assumed``;
- the program's own counts of the window, from its ``serve.decode`` and
  ``serve.prefill`` spans: pools the decode steps' queries scored and
  selected, tail positions they attended unscored, prefill blocks that
  continued a recurrence;
- the selection check (``lib/reference_glm53.py``): as ``lm_serve_dsa``'s,
  over pools: a pool named in part, a missing tail position or a position
  beyond the query is ``wrong`` whatever the gap.

Workload file keys: those of ``lm_serve_dsa``.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import lm_serve, lm_serve_dsa, lm_serve_hybrid
from benchmarks.drivers._moe_common import _check_tree
from benchmarks.lib import loadgen, reference_glm53
from benchmarks.lib.outcome import Outcome

INF = float("inf")
# seeded scales other than Glorot's (the configuration file's
# ``assumed.weights``): the latent layer's, as glm-5.2-l5, and the maps'
SCALES = {"wq_b": 4.0, "wukv_value": 4.0, "wo": 4.0}
HC_PROJECTION_STD = 0.5     # of x~ Phi: Phi is normal x this / sqrt(n D)
HC_ALPHA = (1.0, 0.8, 1.2)
HC_BIAS_STD = 0.5
# the expert bias is balanced on seeded tokens, as noaux_tc balances it in
# training (``balance_router``): tokens, passes over them, first step, decay
BALANCE = {"tokens": 2048, "passes": 48, "step": 0.05, "decay": 0.9}


# ---- the model from its configuration file ----------------------------------
def layer_kinds(config: dict):
    """``(mixers, ffns, indexers)`` of the kept layers."""
    mixers = ["kda" if kind == "linear_attention" else "mla"
              for kind in config["layer_types"]]
    ffns = ["glu" if kind == "dense" else "moe"
            for kind in config["mlp_layer_types"]]
    lin = config["linear_attn_config"]
    n = config["num_hidden_layers"]
    if not (len(mixers) == len(ffns) == n == len(config["kept_layers"])
            == len(config["indexer_types"])):
        raise SystemExit("kept_layers, layer_types, mlp_layer_types, "
                         "indexer_types and num_hidden_layers disagree")
    if ([i for i, m in enumerate(mixers) if m == "kda"] != lin["kda_layers"]
            or [i for i, m in enumerate(mixers) if m == "mla"]
            != lin["full_attn_layers"]):
        raise SystemExit("layer_types and linear_attn_config disagree")
    if ffns.count("glu") != config["first_k_dense_replace"]:
        raise SystemExit("mlp_layer_types and first_k_dense_replace disagree")
    # a KDA layer has no indexer (the configuration's assumed.indexer_types)
    indexers = [kind if m == "mla" else None
                for m, kind in zip(mixers, config["indexer_types"])]
    return mixers, ffns, indexers


def build_lm(config: dict, *, policy: str, seed: int, max_len: int):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    mixers, ffns, indexers = layer_kinds(config)
    lin, share = config["linear_attn_config"], config["share"]
    if lin["num_heads"] != config["num_attention_heads"]:
        raise SystemExit("the program gives both mixers num_attention_heads")
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_layers=len(mixers),
        d_ff=config["moe_intermediate_size"], max_len=max_len, seed=seed,
        dtype_policy=policy, pos_encoding="rope", norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        # the indexer's own RoPE: the model has no other (assumed.indexer_rope)
        rope_theta=config["assumed_sizes"]["index_rope_theta"],
        rope_interleaved=config["indexer_rope_interleave"],
        tie_embeddings=config["tie_word_embeddings"],
        num_experts=config["published"]["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        mixers=mixers, ffns=ffns, indexers=indexers,
        glu_width=config["intermediate_size"],
        hc={"streams": config["hc_mult"],
            "sinkhorn_iters": config["hc_sinkhorn_iters"],
            "eps": config["hc_eps"]},
        kda={"head_dim": lin["head_dim"],
             "conv": lin["short_conv_kernel_size"],
             "lower": float(lin["gate_lower_bound"]),
             "gate_rank": config["assumed_sizes"]["kda_gate_rank"],
             "out_gate": "channel"},
        mla={**{k: config[k] for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim")}, "gate": False},
        dsa={"n_heads": config["index_n_heads"],
             "head_dim": config["index_head_dim"],
             "topk": config["index_topk"],
             "rope_dim": config["assumed_sizes"]["index_rope_dim"],
             "pool": config["index_kpool"]},
        moe={"n_group": config["n_group"],
             "topk_group": config["topk_group"],
             "scale": config["routed_scaling_factor"], "bias": True,
             "shared_width": (config["n_shared_experts"]
                              * config["moe_intermediate_size"]),
             "first": share["first_expert"],
             "held": config["n_routed_experts"],
             "swiglu_limit": config["swiglu_limit"]})


def reference_config(config: dict) -> dict:
    """What ``lib/reference_glm53.py`` reads, from the configuration file."""
    lin, sizes = config["linear_attn_config"], config["assumed_sizes"]
    named = {"kda_heads": lin["num_heads"],
             "gate_lower_bound": float(lin["gate_lower_bound"]),
             "index_rope_dim": sizes["index_rope_dim"],
             "index_rope_theta": sizes["index_rope_theta"]}
    return {**{k: config[k] for k in reference_glm53.KEYS if k not in named},
            **named,
            "share": {"first_expert": config["share"]["first_expert"],
                      "held": config["n_routed_experts"]}}


def _block_init(lm, mixer: str, ffn: str):
    """``key -> block`` for one kind of layer: Glorot-normal matrices (three
    of the latent layer's scaled, ``SCALES``), unit gains, the maps drawn far
    from the identity, the small vectors drawn too."""
    d, h, dt = lm.d_model, lm.num_heads, lm.policy.param_dtype
    n = int(lm.hc["streams"])

    def glorot(key, shape, fan_in, fan_out, scale=1.0):
        std = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dt) * scale
        return jax.random.normal(key, shape, dt) * std

    def dense(key, fan_in, fan_out, scale=1.0):
        return glorot(key, (fan_in, fan_out), fan_in, fan_out, scale)

    def normal(key, shape, scale):
        return jax.random.normal(key, shape, dt) * scale

    def gain(width=d):
        return {"g": jnp.ones((width,), dt)}

    def maps(key):
        k = jax.random.split(key, 2)
        width = 2 * n + n * n
        return {"phi": normal(k[0], (n * d, width),
                              HC_PROJECTION_STD * (n * d) ** -0.5),
                "alpha": jnp.asarray(HC_ALPHA, dt),
                "b": normal(k[1], (width,), HC_BIAS_STD)}

    def init(key):
        k = jax.random.split(key, 32)
        blk = {"ln1": gain(), "ln2": gain(), "hc1": maps(k[24]),
               "hc2": maps(k[25])}
        if mixer == "kda":
            dk, taps, r = (lm.kda["head_dim"], lm.kda["conv"],
                           lm.kda["gate_rank"])
            c = h * dk
            blk["kda"] = {
                "wq": dense(k[0], d, c), "wk": dense(k[1], d, c),
                "wv": dense(k[2], d, c),
                "wa_down": dense(k[3], d, r), "wa_up": dense(k[26], r, c),
                "wb": dense(k[4], d, h),
                "wg_down": dense(k[5], d, r), "wg_up": dense(k[27], r, c),
                "wo": dense(k[6], c, d),
                "conv_q": normal(k[7], (taps, c), taps ** -0.5),
                "conv_k": normal(k[8], (taps, c), taps ** -0.5),
                "conv_v": normal(k[9], (taps, c), taps ** -0.5),
                "a_log": normal(k[10], (h,), 0.3),
                "dt_bias": normal(k[11], (c,), 0.5),
                "o_norm": gain(dk)}
        else:
            m = lm.mla
            rq, r, dn, dv = (m["q_lora_rank"], m["kv_lora_rank"],
                             m["qk_nope_head_dim"], m["v_head_dim"])
            hi, di = lm.dsa["n_heads"], lm.dsa["head_dim"]
            wukv = dense(k[3], r, h * (dn + dv)).reshape(r, h, dn + dv)
            wukv = wukv.at[..., dn:].multiply(SCALES["wukv_value"])
            blk["mla"] = {
                "wq_a": dense(k[0], d, rq), "q_norm": gain(rq),
                "wq_b": dense(k[1], rq, h * dn, SCALES["wq_b"]),
                "wdkv": dense(k[2], d, r), "kv_norm": gain(r),
                "wukv": wukv.reshape(r, -1),
                "wo": dense(k[4], h * dv, d, SCALES["wo"]),
                "indexer": {
                    "wq": dense(k[5], rq, hi * di), "wk": dense(k[6], d, di),
                    "k_norm": {"g": jnp.ones((di,), dt),
                               "b": jnp.zeros((di,), dt)},
                    "ww": dense(k[7], d, hi)}}
        if ffn == "glu":
            g = lm.glu_width
            blk["glu"] = {"w1": dense(k[12], d, g), "w3": dense(k[13], d, g),
                          "w2": dense(k[14], g, d)}
        else:
            e, held, f = lm.num_experts, lm.experts_held, lm.d_ff
            w = lm.moe["shared_width"]
            blk["moe"] = {
                "router": dense(k[15], d, e),
                "bias": normal(k[16], (e,), 0.01),
                "w_gate": glorot(k[17], (held, d, f), d, f),
                "w_up": glorot(k[18], (held, d, f), d, f),
                "w_down": glorot(k[19], (held, f, d), f, d),
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
    kinds = list(zip(lm.mixers, lm.ffns))
    inits = {kind: _block_init(lm, *kind) for kind in set(kinds)}

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": jax.random.normal(k[0], (v, d), dt) * 0.02,
                "head": jax.random.normal(k[1], (v, d), dt) * 0.02,
                "ln_f": {"g": jnp.ones((d,), dt)}}

    keys = jax.random.split(jax.random.PRNGKey(seed), lm.num_layers + 1)
    _check_tree(lm, {
        **jax.eval_shape(ends, keys[0]),
        "blocks": [jax.eval_shape(inits[kind], keys[0]) for kind in kinds]})
    params = ends(keys[0])
    params["blocks"] = [inits[kind](keys[1 + i])
                        for i, kind in enumerate(kinds)]
    return params


def balance_router(params, cfg: dict, seed: int):
    """The expert bias of every routed layer, moved as ``noaux_tc`` moves it
    in training, by the benchmark's own float32 reference
    (``reference_glm53.balanced_biases``: one forward over
    ``BALANCE["tokens"]`` seeded token ids, each layer's bias balanced on its
    own input) and by nothing of the program under test: the weights are a
    function of the seed and of files under ``benchmarks/`` alone. A trained
    router is balanced by this very bias; a seeded one is not: the normed
    hidden states of a random model share a large component, so nearly every
    token prefers the same few of the 288 experts, and whether the nine held
    here are among them is a coin a seed and layer (six seeds of the
    unbalanced cell read 0.067-0.278 pairs here a token and 0.59-2.22
    experts reached a decode step, and TPOT moved with them: my chip run, PR
    51, call 1). Returns ``(params, max over mean load at the balanced
    bias)``."""
    tokens = jax.random.randint(jax.random.PRNGKey(seed ^ 0x5EED),
                                (BALANCE["tokens"],), 1, cfg["vocab_size"])
    biases, skew = reference_glm53.balanced_biases(
        params, tokens, reference_config(cfg), BALANCE["passes"],
        BALANCE["step"], BALANCE["decay"])
    layers = [i for i, blk in enumerate(params["blocks"]) if "moe" in blk]
    blocks = list(params["blocks"])
    for bias, i in zip(biases, layers):
        moe = blocks[i]["moe"]
        blocks[i] = {**blocks[i],
                     "moe": {**moe, "bias": bias.astype(moe["bias"].dtype)}}
    return {**params, "blocks": blocks}, skew


def build_model(ctx):
    sv = ctx.cell["server"]
    lm = build_lm(ctx.config, policy=sv["policy"], seed=ctx.seed,
                  max_len=int(sv["max_len"]))
    lm.params, skew = balance_router(make_params(lm, ctx.seed), ctx.config,
                                     ctx.seed)
    # the knee tools hand a context without ``log``
    print(f"weights: expert load max over mean after balancing "
          f"{float(skew):.3f} ({BALANCE['passes']} passes of "
          f"{BALANCE['tokens']} tokens)", flush=True)
    return lm


# ---- the window --------------------------------------------------------------
build_server = lm_serve_hybrid.build_server     # what the knee tools call


def serve_window(ctx, lm):
    """``lm_serve_dsa.serve_window`` with the pooled indexer's and the block
    prefill's counts of the window's own spans beside its counters."""
    from deeplearning4j_tpu.monitor import trace as program_trace

    seen = {"pools_scored": 0, "pools_selected": 0, "tail_attended": 0,
            "recurrence_blocks": 0}

    def sink(span):
        if ctx.t_window is None or ctx.t_window_end is not None:
            return
        attrs = span["attrs"]
        if span["name"] == "serve.decode" and "pools_scored" in attrs:
            for name in ("pools_scored", "pools_selected", "tail_attended"):
                seen[name] += attrs[name]
        elif span["name"] == "serve.prefill" and "blocks" in attrs:
            # every block of a prompt but its first continues the slot's
            # four recurrences
            seen["recurrence_blocks"] += attrs["blocks"] - 1

    program_trace.add_sink(sink)
    try:
        res, counters, rng = lm_serve_dsa.serve_window(ctx, lm)
    finally:
        program_trace.remove_sink(sink)
    steps = max(1, counters["decode_steps"])
    layers = len(lm.layers_of("mla"))
    counters.update({
        **seen,
        "pools_scored_per_step": seen["pools_scored"] / steps,
        "pools_selected_per_step": seen["pools_selected"] / steps,
        "tail_attended_per_step": seen["tail_attended"] / steps,
        # pools scored over positions up to the cursor, both a latent layer
        "dsa_pools_scored_share": seen["pools_scored"] / max(
            1, counters["keys_cached"]),
        "latent_layers": layers,
    })
    return res, counters, rng


def check_against_reference(lm, cfg, finished, check, traffic, rng):
    """``lm_serve_hybrid.check_against_reference`` (routing and tokens; 3
    seeded finished requests and the longest) with a reference that is
    handed each sampled request's recorded selections, and the selections'
    own verdict; as ``lm_serve_dsa``'s, the limits over pools. The reference
    takes a sequence in blocks and holds the traffic's ``max_total_tokens``
    positions (one compile), so the longest request the traffic can make is
    judged like any other."""
    by_seq, verdicts = {}, []
    for o in finished:
        toks = np.asarray(o.request.tokens, np.int32)
        seq = np.concatenate([o.arrival.prompt, toks])[:-1]
        by_seq[seq.tobytes()] = o

    def forward_tail(params, seq, cfg, n_tail, pad_to=None, chosen=None):
        o = by_seq[np.asarray(seq).tobytes()]
        selected = np.concatenate(o.request.selection, axis=1)
        logits, routes, picks = reference_glm53.forward_tail(
            params, seq, cfg, n_tail, pad_to=pad_to, chosen=chosen,
            selected=selected)
        verdicts.append((len(o.arrival.prompt), selected.shape[1], picks))
        return logits, routes

    ok, notes = lm_serve_hybrid.check_against_reference(
        lm, cfg, finished, check, traffic, rng, forward_tail=forward_tail)
    for prompt, n, picks in verdicts:
        shortfall = np.stack([np.asarray(p[0]) for p in picks])   # [Lmla, n]
        wrong = int(sum(int(np.asarray(p[1]).sum()) for p in picks))
        overlap = np.stack([np.asarray(p[2]) for p in picks])
        beyond = int(np.sum(shortfall > check["select_gap"]))
        apart = int(np.sum(overlap < check["select_overlap"]))
        ok &= wrong == 0 and beyond == 0 and apart == 0
        notes.append(f"check: prompt={prompt} selections_judged="
                     f"{shortfall.size} off_reference="
                     f"{int(np.sum(shortfall > 0))} worst_select_shortfall="
                     f"{float(shortfall.max()):.5f} beyond_select_gap="
                     f"{beyond} least_select_overlap="
                     f"{float(overlap.min()):.4f} mean="
                     f"{float(overlap.mean()):.4f} below_select_overlap="
                     f"{apart} wrong_selections={wrong}")
    return bool(ok), notes


def run(ctx) -> Outcome:
    cfg, cell = ctx.config, ctx.cell
    if cell["loop"]["cut_at_seconds"]:
        raise SystemExit("lm_serve_hc drains: no cell of it cuts its window")
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
             f"hc: pools_scored_share="
             f"{counters['dsa_pools_scored_share']:.4f} "
             f"pools_scored_per_step={counters['pools_scored_per_step']:.0f} "
             f"pools_selected_per_step="
             f"{counters['pools_selected_per_step']:.0f} "
             f"tail_attended_per_step="
             f"{counters['tail_attended_per_step']:.2f} "
             f"keys_cached_per_step={counters['keys_cached_per_step']:.0f} "
             f"keys_attended_per_step="
             f"{counters['keys_attended_per_step']:.0f} "
             f"prefill_blocks_per_request="
             f"{counters['prefill_blocks_per_request']:.2f} "
             f"recurrence_blocks={counters['recurrence_blocks']} "
             f"routed_pairs={counters['moe_routed_pairs']} "
             f"pairs_here_per_token="
             f"{counters['routed_pairs_here_per_token']:.4f} "
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
