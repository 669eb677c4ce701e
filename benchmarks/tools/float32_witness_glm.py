"""Why a bf16 server's selections differ from the float32 reference's
(``glm-serve-longdoc``'s ``select_gap`` and ``select_overlap``): the same
weights and the same prompt through ``DecodeServer`` twice, once at the
cell's bf16 policy and once at the float32 policy with ``highest`` matmul
precision, each judged as the cell judges a request
(``reference_glm_dsa.forward_tail(chosen=, selected=)``). If the shortfall of
the layers behind the first one comes from bf16 hidden states and not from the
selection's code, the float32 run reads a shortfall near 0 and an overlap near
1 in every layer, and the bf16 run the cell's readings.

    python3 benchmarks/tools/float32_witness_glm.py [--prompt 4096] [--new 48] [--seed 7]
    JAX_PLATFORMS=cpu python3 benchmarks/tools/float32_witness_glm.py --rehearse --prompt 40 --new 8

One process, a small pool (2 slots of 8,192 positions, one rung) so that the
float32 program's temporaries fit beside the weights; the model, its weights
and the reference's configuration are the cell's driver's. Needs a TPU: on a
CPU (``--rehearse``: the workload file's rehearsal sizes, for the control
flow) bf16 matmuls accumulate otherwise and nothing here is a chip number.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def judged(lm, cfg, req):
    """Per layer with an indexer ``(worst shortfall, least overlap, mean
    overlap, wrong)`` of the request's recorded selections, and the worst
    token gap, against the reference that follows its experts and keys."""
    import numpy as np

    from benchmarks.lib import reference_glm_dsa as ref

    toks = np.asarray(req.tokens, np.int32)
    seq = np.concatenate([req.prompt, toks])[:-1]
    experts = np.concatenate([r[0] for r in req.routing], axis=1)
    selected = np.concatenate(req.selection, axis=1)
    logits, _, picks = ref.forward_tail(
        lm.params, seq, cfg, len(toks), chosen=experts, selected=selected)
    logits = np.asarray(logits)
    gap = (logits.max(-1) - logits[np.arange(len(toks)), toks]) \
        / np.abs(logits).max(-1)
    return float(gap.max()), [
        (float(np.max(s)), float(np.min(o)), float(np.mean(o)),
         int(np.sum(w))) for s, w, o in picks]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--prompt", type=int, default=4096)
    ap.add_argument("--new", type=int, default=48)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks import run as harness
    from benchmarks.drivers import lm_serve_dsa as drv
    from deeplearning4j_tpu.serving import DecodeServer

    with open(os.path.join(ROOT, "benchmarks/configs/glm-5.2-l5.json")) as f:
        config = json.load(f)
    bucket = -(-args.prompt // 2048) * 2048
    if args.rehearse:
        with open(os.path.join(
                ROOT, "benchmarks/workloads/glm-serve-longdoc.json")) as f:
            _, config = harness._apply_rehearsal(json.load(f), config)
        bucket = 64
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit("float32_witness_glm: needs a TPU")
    cfg = drv.reference_config(config)
    prompt = np.random.default_rng(args.seed).integers(
        1, config["vocab_size"], args.prompt).astype(np.int32)
    params = None
    for policy, precision in (("bf16", None), ("float32", "highest")):
        lm = drv.build_lm(config, policy=policy, seed=args.seed,
                          max_len=2 * bucket)
        lm.params = params = params or drv.make_params(lm, args.seed)
        with jax.default_matmul_precision(precision or "default"):
            server = DecodeServer(lm, slots=2, max_len=2 * bucket,
                                  buckets=(bucket,), fuse_steps=1,
                                  record_routing=True)
            req = server.submit(prompt, args.new)
            server.drain()
        del server
        gc.collect()
        gap, layers = judged(lm, cfg, req)
        print(json.dumps({
            "policy": policy, "matmul_precision": precision or "default",
            "prompt": args.prompt, "new": args.new,
            "worst_token_gap": round(gap, 5),
            "layers_with_an_indexer": [
                {"worst_select_shortfall": round(s, 5),
                 "least_select_overlap": round(lo, 4),
                 "mean_select_overlap": round(mean, 4), "wrong": w}
                for s, lo, mean, w in layers]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
