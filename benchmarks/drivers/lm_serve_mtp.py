"""Driver ``lm_serve_mtp``: ``lm_serve``'s open loop for a model that the
server drafts for from its own multi-token-prediction module
(``gigachat3.1-702b-a36b-l5``: latent attention with a compressed query and a
rotary part scaled by YaRN, group-limited sigmoid routing with a shared
expert, one chip's share of the experts and of the vocabulary, and the
module), on one chip: every decode dispatch is a speculative round.

The window (warm-up, schedule, clock, the server that records its routing) is
``lm_serve_hybrid.serve_window`` and the routing-and-token check
``lm_serve_hybrid.check_against_reference``, both by import; the seeded block
of a layer and its scales are ``lm_serve_dsa``'s. This driver brings what the
model changes:

- its builder: ``TransformerLM`` from the configuration file (``rope_scaling``,
  the experts held of the router's, ``mtp=``), and its weights, made on the
  device from the seed one block at a time, the module's block as a layer's;
- the program's own counts of the window's rounds, from its ``serve.decode``
  spans: ``rounds``, ``proposed``, ``accepted``, ``emitted``;
- the draft check (``lib/reference_gigachat_mtp.py``): the reference computes
  each sampled sequence with the experts that the window's own prefill blocks
  and rounds chose at every position, the module's layer among them, and
  every draft that a round of the request verified (``ServeRequest.drafts``)
  has to be the argmax, or within ``near_tie`` x max|logit| of it, of the
  reference module's logits at the position it was proposed from.

Workload file keys: those of ``lm_serve_moe``.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import lm_serve, lm_serve_dsa, lm_serve_hybrid
from benchmarks.drivers._moe_common import _check_tree
from benchmarks.lib import loadgen, reference_gigachat_mtp
from benchmarks.lib.outcome import Outcome

INF = float("inf")


# ---- the model from its configuration file ----------------------------------
def build_lm(config: dict, *, policy: str, seed: int, max_len: int,
             module: bool = True):
    """``module=False``: the same model without its module, served a token a
    step (``tools/plain_decode_mtp.py``: what a round costs over a step)."""
    from deeplearning4j_tpu.models.transformer import TransformerLM

    n, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    if n != len(config["kept_layers"]):
        raise SystemExit("kept_layers and num_hidden_layers disagree")
    if config["num_nextn_predict_layers"] != 1:
        raise SystemExit("one multi-token-prediction module is written")
    share = config["share"]
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_layers=n,
        d_ff=config["moe_intermediate_size"], max_len=max_len, seed=seed,
        dtype_policy=policy, pos_encoding="rope", norm="rmsnorm",
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_interleaved=config["rope_interleave"],
        rope_scaling=config["rope_scaling"],
        tie_embeddings=config["tie_word_embeddings"],
        num_experts=config["published"]["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        mixers=["mla"] * n, ffns=["glu"] * dense + ["moe"] * (n - dense),
        glu_width=config["intermediate_size"],
        mla={**{k: config[k] for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim")}, "gate": False},
        moe={"n_group": config["n_group"],
             "topk_group": config["topk_group"],
             "scale": config["routed_scaling_factor"], "bias": True,
             "shared_width": (config["n_shared_experts"]
                              * config["moe_intermediate_size"]),
             "first": share["first_expert"],
             "held": config["n_routed_experts"]},
        mtp={"loss_weight": config["mtp_loss_weight"]} if module else None)


def reference_config(config: dict) -> dict:
    """What ``lib/reference_gigachat_mtp.py`` reads, from the configuration
    file."""
    return {**{k: config[k] for k in reference_gigachat_mtp.KEYS},
            "rope_scaling": config["rope_scaling"],
            "share": {"first_expert": config["share"]["first_expert"],
                      "held": config["n_routed_experts"]}}


def _block_init(lm, ffn: str):
    """``lm_serve_dsa._block_init`` with ``wq_b`` divided by YaRN's softmax
    multiplier (``mla["softmax_mult"]``, 2.0047 here): a trained model's
    weights absorb the factor, seeded ones do not, and with GLM's ``wq_b`` x 4
    under it the attention logits double, attention turns winner-take-all and
    bf16 rounding flips its winner (the configuration's ``assumed.weights``;
    PERF.md section 6, PR 35). So the seeded attention is as sharp as
    ``glm-5.2-l5``'s, whose check limits this cell shares."""
    init = lm_serve_dsa._block_init(lm, ffn, None)
    mult = float(lm.mla.get("softmax_mult", 1.0))

    def scaled(key):
        blk = init(key)
        blk["mla"]["wq_b"] = blk["mla"]["wq_b"] / mult
        return blk

    return jax.jit(scaled)


def make_params(lm, seed: int):
    """Weights on the device from ``seed``: one jitted call a block (one
    compile a kind of block; ``_block_init``: Glorot-normal with its three
    scaled matrices), the module's block drawn as a layer of the
    last kind, and one call for the embedding, the head, ``M`` and the norms.
    ``init()`` itself is never called: its Adam moments would not fit."""
    v, d, dt = lm.vocab_size, lm.d_model, lm.policy.param_dtype
    kinds = list(lm.ffns) + ([lm.ffns[-1]] if lm.mtp else [])
    inits = {kind: _block_init(lm, kind) for kind in set(kinds)}

    def gain():
        return {"g": jnp.ones((d,), dt)}

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 3)
        out = {"embed": jax.random.normal(k[0], (v, d), dt) * 0.02,
               "head": jax.random.normal(k[1], (v, d), dt) * 0.02,
               "ln_f": gain()}
        if lm.mtp:
            out["mtp"] = {
                "enorm": gain(), "hnorm": gain(), "norm": gain(),
                "proj": jax.random.normal(k[2], (2 * d, d), dt)
                * jnp.sqrt(2.0 / (3 * d)).astype(dt)}
        return out

    keys = jax.random.split(jax.random.PRNGKey(seed), len(kinds) + 1)
    shapes = [jax.eval_shape(inits[kind], keys[0]) for kind in kinds]
    want = jax.eval_shape(ends, keys[0])
    want["blocks"] = shapes[:lm.num_layers]
    if lm.mtp:
        want["mtp"]["block"] = shapes[-1]
    _check_tree(lm, want)
    params = ends(keys[0])
    blocks = [inits[kind](keys[1 + i]) for i, kind in enumerate(kinds)]
    params["blocks"] = blocks[:lm.num_layers]
    if lm.mtp:
        params["mtp"]["block"] = blocks[-1]
    return params


def build_model(ctx):
    sv = ctx.cell["server"]
    lm = build_lm(ctx.config, policy=sv["policy"], seed=ctx.seed,
                  max_len=int(sv["max_len"]),
                  module=bool(ctx.cell.get("module", True)))
    lm.params = make_params(lm, ctx.seed)
    return lm


# ---- the window --------------------------------------------------------------
build_server = lm_serve_hybrid.build_server     # what the knee tools call


def serve_window(ctx, lm):
    """``lm_serve_hybrid.serve_window`` with the rounds' counts of the
    window's own ``serve.decode`` spans beside its counters."""
    from deeplearning4j_tpu.monitor import trace as program_trace

    seen = {"rounds": 0, "proposed": 0, "accepted": 0, "emitted": 0}

    def sink(span):
        if (ctx.t_window is None or ctx.t_window_end is not None
                or span["name"] != "serve.decode"
                or "rounds" not in span["attrs"]):
            return
        for k in seen:
            seen[k] += span["attrs"][k]

    program_trace.add_sink(sink)
    try:
        res, counters, rng = lm_serve_hybrid.serve_window(ctx, lm)
    finally:
        program_trace.remove_sink(sink)
    counters.update({"spec_" + k: v for k, v in seen.items()})
    if seen["rounds"]:
        counters["spec_tokens_per_round"] = seen["emitted"] / seen["rounds"]
        counters["spec_accept_share"] = seen["accepted"] / seen["proposed"]
    return res, counters, rng


def check_against_reference(lm, cfg, finished, check, traffic, rng):
    """``lm_serve_hybrid.check_against_reference`` (routing and tokens, the
    module's layer among the routed ones) with the drafts' own verdict; see
    the module's docstring."""
    by_seq, verdicts = {}, []
    for o in finished:
        toks = np.asarray(o.request.tokens, np.int32)
        by_seq[np.concatenate([o.arrival.prompt, toks])[:-1].tobytes()] = o

    def forward_tail(params, seq, cfg, n_tail, pad_to=None, chosen=None):
        o = by_seq[np.asarray(seq).tobytes()]
        logits, extra, routes = reference_gigachat_mtp.forward_tail(
            params, seq, cfg, n_tail, pad_to=pad_to, chosen=chosen,
            after=int(o.request.tokens[-1]))
        verdicts.append((o, np.asarray(extra)))
        return logits, routes

    ok, notes = lm_serve_hybrid.check_against_reference(
        lm, cfg, finished, check, traffic, rng, forward_tail=forward_tail)
    for o, extra in verdicts:
        real = len(o.arrival.prompt) + len(o.request.tokens) - 1
        start = real - len(extra)
        # a draft for position q was proposed from position q - 2 (its hidden
        # state and token q - 1); those proposed for a position past the
        # request's end have no token after them to be made from
        judged = [(q - 2 - start, d) for q, d in o.request.drafts
                  if start <= q - 2 < real]
        rows = extra[[j for j, _ in judged]]
        drafts = np.asarray([d for _, d in judged])
        gap = (rows.max(-1) - rows[np.arange(len(judged)), drafts]
               ) / np.abs(rows).max(-1)
        bad = int(np.sum(gap > check["near_tie"]))
        ok &= bad == 0 and len(judged) > 0
        notes.append(f"check: prompt={len(o.arrival.prompt)} drafts_judged="
                     f"{len(judged)} of {len(o.request.drafts)} "
                     f"off_argmax={int(np.sum(gap > 0))} "
                     f"worst_draft_gap={float(gap.max()):.5f} "
                     f"drafts_beyond_near_tie={bad}")
    return bool(ok), notes


def run(ctx) -> Outcome:
    cfg, cell = ctx.config, ctx.cell
    if cell["loop"]["cut_at_seconds"]:
        raise SystemExit("lm_serve_mtp drains: no cell of it cuts its window")
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
    # the mean cursor of a round's live slot: a finished request's j-th
    # decode position sits at prompt_len + j
    ctx_sum = sum(len(o.request.tokens) * len(o.arrival.prompt)
                  + len(o.request.tokens) * (len(o.request.tokens) - 1) // 2
                  for o in finished)
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
        "decode_context_mean": ctx_sum / max(1, done_tokens),
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
             f"mtp: rounds={counters['spec_rounds']} "
             f"proposed={counters['spec_proposed']} "
             f"accepted={counters['spec_accepted']} "
             f"emitted={counters['spec_emitted']} "
             f"routed_pairs={counters['moe_routed_pairs']} "
             f"pairs_here_per_token="
             f"{counters['routed_pairs_here_per_token']:.4f} "
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
    if finished and lm.mtp:
        ref_ok, ref_notes = check_against_reference(
            lm, reference_config(cfg), finished, cell["check"],
            cell["traffic"], rng)
        ok &= ref_ok
        notes += ref_notes
    return Outcome(
        correct=ok, attempted=len(res.offered), failed=failed,
        end_to_end={"serve_tpot_p50_ms": counters["tpot_p50_ms"]},
        counters=counters, notes=notes)
