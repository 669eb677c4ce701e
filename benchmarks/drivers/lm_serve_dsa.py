"""Driver ``lm_serve_dsa``: ``lm_serve``'s open loop for a model with learned
sparse attention (``glm-5.2-l5``: latent attention with a compressed query
over a lightning indexer's selection, sigmoid routing with a shared expert,
one chip's share of the experts and of the vocabulary), on one chip.

The window (warm-up, schedule, clock, the server that records its routing) is
``lm_serve_hybrid.serve_window`` and the routing-and-token check
``lm_serve_hybrid.check_against_reference``, both by import: that check takes
the reference as an argument. This driver brings what the model changes:

- its builder: ``TransformerLM`` from the configuration file (the kept
  layers' ``mlp_layer_types`` and ``indexer_types``, the experts held of the
  router's), and its weights, made on the device from the seed one block at a
  time, with the scales the configuration file states under ``assumed``;
- the program's own counts of the window, from its ``serve.decode`` and
  ``serve.prefill`` spans: latent rows the live slots held and rows their
  queries attended (``keys_cached``, ``keys_attended``), prefill blocks;
- the selection check (``lib/reference_glm_dsa.py``): for the sampled
  requests the key positions that the window's own programs selected, for the
  query that emitted each generated token, go to the reference with the
  experts; the reference computes the model with those keys at those
  positions and says how far each selection falls short of its own
  (``select_gap``; a position beyond the query, named twice or missing is
  ``wrong`` whatever the gap) and how much of it the reference's own
  selection holds too (``select_overlap``, the least share allowed, one
  number or one for each layer with an indexer: a top-k below recall 1 fails
  here whatever its weakest key scores). The prompt's
  other positions keep the reference's own selection: theirs would be 8 KiB
  a position and layer.

Workload file keys: those of ``lm_serve_moe`` and ``check.{select_gap,
select_overlap, longest_max_prompt, pad_to}``.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import lm_serve, lm_serve_hybrid
from benchmarks.drivers._moe_common import _check_tree
from benchmarks.lib import loadgen, reference_glm_dsa
from benchmarks.lib.outcome import Outcome

INF = float("inf")
# seeded scales other than Glorot's (the configuration file's ``assumed``)
SCALES = {"wq_b": 4.0, "wukv_value": 4.0, "wo": 4.0}


# ---- the model from its configuration file ----------------------------------
def build_lm(config: dict, *, policy: str, seed: int, max_len: int):
    from deeplearning4j_tpu.models.transformer import TransformerLM

    ffns = ["glu" if kind == "dense" else "moe"
            for kind in config["mlp_layer_types"]]
    indexers = list(config["indexer_types"])
    if not (len(ffns) == len(indexers) == config["num_hidden_layers"]
            == len(config["kept_layers"])):
        raise SystemExit("kept_layers, mlp_layer_types, indexer_types and "
                         "num_hidden_layers disagree")
    if ffns.count("glu") != config["first_k_dense_replace"]:
        raise SystemExit("mlp_layer_types and first_k_dense_replace disagree")
    share = config["share"]
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_layers=len(ffns),
        d_ff=config["moe_intermediate_size"], max_len=max_len, seed=seed,
        dtype_policy=policy, pos_encoding="rope", norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_parameters"]["rope_theta"],
        rope_interleaved=config["rope_interleave"],
        tie_embeddings=config["tie_word_embeddings"],
        num_experts=config["published"]["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        mixers=["mla"] * len(ffns), ffns=ffns, indexers=indexers,
        glu_width=config["intermediate_size"],
        mla={**{k: config[k] for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim")}, "gate": False},
        dsa={"n_heads": config["index_n_heads"],
             "head_dim": config["index_head_dim"],
             "topk": config["index_topk"],
             "rope_dim": config["qk_rope_head_dim"]},
        moe={"n_group": config["n_group"],
             "topk_group": config["topk_group"],
             "scale": config["routed_scaling_factor"], "bias": True,
             "shared_width": (config["n_shared_experts"]
                              * config["moe_intermediate_size"]),
             "first": share["first_expert"],
             "held": config["n_routed_experts"]})


def reference_config(config: dict) -> dict:
    """What ``lib/reference_glm_dsa.py`` reads, from the configuration file."""
    keep = [k for k in reference_glm_dsa.KEYS
            if k not in ("rope_theta", "index_rope_dim")]
    return {**{k: config[k] for k in keep},
            "rope_theta": config["rope_parameters"]["rope_theta"],
            "index_rope_dim": config["qk_rope_head_dim"],
            "share": {"first_expert": config["share"]["first_expert"],
                      "held": config["n_routed_experts"]}}


def _block_init(lm, ffn: str, indexer: str):
    """``key -> block`` for one kind of layer: Glorot-normal matrices (three
    of them scaled, ``SCALES``), unit gains, expert bias x 0.01."""
    d, h, dt = lm.d_model, lm.num_heads, lm.policy.param_dtype
    m = lm.mla
    rq, r, dn, dr, dv = (m["q_lora_rank"], m["kv_lora_rank"],
                         m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                         m["v_head_dim"])

    def glorot(key, shape, fan_in, fan_out, scale=1.0):
        std = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dt) * scale
        return jax.random.normal(key, shape, dt) * std

    def dense(key, fan_in, fan_out, scale=1.0):
        return glorot(key, (fan_in, fan_out), fan_in, fan_out, scale)

    def gain(width=d):
        return {"g": jnp.ones((width,), dt)}

    def init(key):
        k = jax.random.split(key, 24)
        # keys as stored, values scaled: [r, H, dn + dv] with the last dv
        # columns of each head the values
        wukv = dense(k[3], r, h * (dn + dv)).reshape(r, h, dn + dv)
        wukv = wukv.at[..., dn:].multiply(SCALES["wukv_value"])
        mla = {"wq_a": dense(k[0], d, rq), "q_norm": gain(rq),
               "wq_b": dense(k[1], rq, h * (dn + dr), SCALES["wq_b"]),
               "wdkv": dense(k[2], d, r + dr), "kv_norm": gain(r),
               "wukv": wukv.reshape(r, -1),
               "wo": dense(k[4], h * dv, d, SCALES["wo"])}
        if indexer == "full":
            hi, di = lm.dsa["n_heads"], lm.dsa["head_dim"]
            mla["indexer"] = {
                "wq": dense(k[5], rq, hi * di), "wk": dense(k[6], d, di),
                "k_norm": {"g": jnp.ones((di,), dt),
                           "b": jnp.zeros((di,), dt)},
                "ww": dense(k[7], d, hi)}
        blk = {"ln1": gain(), "ln2": gain(), "mla": mla}
        if ffn == "glu":
            g = lm.glu_width
            blk["glu"] = {"w1": dense(k[12], d, g), "w3": dense(k[13], d, g),
                          "w2": dense(k[14], g, d)}
        else:
            e, n, f = lm.num_experts, lm.experts_held, lm.d_ff
            w = lm.moe["shared_width"]
            blk["moe"] = {
                "router": dense(k[15], d, e),
                "bias": jax.random.normal(k[16], (e,), dt) * 0.01,
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
    kinds = list(zip(lm.ffns, lm.indexers))
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


def build_model(ctx):
    sv = ctx.cell["server"]
    lm = build_lm(ctx.config, policy=sv["policy"], seed=ctx.seed,
                  max_len=int(sv["max_len"]))
    lm.params = make_params(lm, ctx.seed)
    return lm


# ---- the window --------------------------------------------------------------
build_server = lm_serve_hybrid.build_server     # what the knee tools call


def serve_window(ctx, lm):
    """``lm_serve_hybrid.serve_window`` with the sparse-attention counts of
    the window's own spans (nothing of the warm-up: the window has not begun
    then) beside its counters."""
    from deeplearning4j_tpu.monitor import trace as program_trace

    seen = {"keys_cached": 0, "keys_attended": 0, "steps": 0, "blocks": 0,
            "prefills": 0}

    def sink(span):
        if ctx.t_window is None or ctx.t_window_end is not None:
            return
        attrs = span["attrs"]
        if span["name"] == "serve.decode" and "keys_cached" in attrs:
            seen["keys_cached"] += attrs["keys_cached"]
            seen["keys_attended"] += attrs["keys_attended"]
            seen["steps"] += 1
        elif span["name"] == "serve.prefill" and "blocks" in attrs:
            seen["blocks"] += attrs["blocks"]
            seen["prefills"] += 1

    program_trace.add_sink(sink)
    try:
        res, counters, rng = lm_serve_hybrid.serve_window(ctx, lm)
    finally:
        program_trace.remove_sink(sink)
    steps = max(1, seen["steps"])
    counters.update({
        "keys_cached": seen["keys_cached"],
        "keys_attended": seen["keys_attended"],
        "keys_cached_per_step": seen["keys_cached"] / steps,
        "keys_attended_per_step": seen["keys_attended"] / steps,
        "dsa_keys_attended_share": (seen["keys_attended"]
                                    / max(1, seen["keys_cached"])),
        "prefill_blocks_per_request": (seen["blocks"]
                                       / max(1, seen["prefills"])),
    })
    return res, counters, rng


def check_against_reference(lm, cfg, finished, check, traffic, rng):
    """``lm_serve_hybrid.check_against_reference`` (routing and tokens) with
    a reference that is handed each sampled request's recorded selections,
    and the selections' own verdict; see the module's docstring."""
    limit = int(check["longest_max_prompt"])
    judged = [o for o in finished if len(o.arrival.prompt) <= limit]
    notes = []
    if len(judged) < len(finished):
        notes.append(f"check: {len(finished) - len(judged)} finished requests "
                     f"with prompts over {limit} tokens are not sampled")
    if not judged:
        return False, notes + ["check: no finished request to judge"]
    by_seq, verdicts = {}, []
    for o in judged:
        toks = np.asarray(o.request.tokens, np.int32)
        seq = np.concatenate([o.arrival.prompt, toks])[:-1]
        by_seq[seq.tobytes()] = o

    def forward_tail(params, seq, cfg, n_tail, pad_to=None, chosen=None):
        o = by_seq[np.asarray(seq).tobytes()]
        selected = np.concatenate(o.request.selection, axis=1)
        logits, routes, picks = reference_glm_dsa.forward_tail(
            params, seq, cfg, n_tail, pad_to=pad_to, chosen=chosen,
            selected=selected)
        verdicts.append((len(o.arrival.prompt), selected.shape[1], picks))
        return logits, routes

    ok, more = lm_serve_hybrid.check_against_reference(
        lm, cfg, judged, check,
        {"max_total_tokens": int(check["pad_to"]),
         "output_tokens": traffic["output_tokens"]}, rng,
        forward_tail=forward_tail)
    notes += more
    for prompt, n, picks in verdicts:
        shortfall = np.stack([np.asarray(p[0]) for p in picks])   # [Lfull, n]
        wrong = int(sum(int(np.asarray(p[1]).sum()) for p in picks))
        overlap = np.stack([np.asarray(p[2]) for p in picks])
        beyond = int(np.sum(shortfall > check["select_gap"]))
        apart = int(np.sum(overlap < np.broadcast_to(
            np.asarray(check["select_overlap"], np.float64),
            overlap.shape[:1])[:, None]))
        ok &= wrong == 0 and beyond == 0 and apart == 0
        notes.append(f"check: prompt={prompt} selections_judged="
                     f"{shortfall.size} off_reference="
                     f"{int(np.sum(shortfall > 0))} worst_select_shortfall="
                     + " ".join(f"{float(x.max()):.5f}" for x in shortfall)
                     + f" beyond_select_gap={beyond} least_select_overlap="
                     + " ".join(f"{float(x.min()):.4f}" for x in overlap)
                     + f" mean={float(overlap.mean()):.4f} "
                     f"below_select_overlap={apart} "
                     f"wrong_selections={wrong}")
    return bool(ok), notes


def run(ctx) -> Outcome:
    cfg, cell = ctx.config, ctx.cell
    if cell["loop"]["cut_at_seconds"]:
        raise SystemExit("lm_serve_dsa drains: no cell of it cuts its window")
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
             f"dsa: keys_attended_share="
             f"{counters['dsa_keys_attended_share']:.4f} "
             f"keys_cached_per_step={counters['keys_cached_per_step']:.0f} "
             f"keys_attended_per_step="
             f"{counters['keys_attended_per_step']:.0f} "
             f"prefill_blocks_per_request="
             f"{counters['prefill_blocks_per_request']:.2f} "
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
