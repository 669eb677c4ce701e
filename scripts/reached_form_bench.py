"""Time the routed experts' reached form against the dense form on the
attached TPU: the table ``routed_experts._reached_blocks``'s rule is read off.

    chiprun -- python3 scripts/reached_form_bench.py [--rows 16,32,64,128,256] [--layers 4]

One layer's experts as ``routed_ffn`` runs them in a trace that takes no
gradient -- float32 storage, bf16 rows, the float32 router, the load and the
combine weights made in the program, then either ``_dense_experts`` on the
cast matrices or ``pallas/reached_experts.py`` on the stored ones -- at
OLMoE's widths (D 2,048, F 1,024, 64 experts, 8 a row) and Mellum2's
(D 2,304, F 896). ``--layers`` layers with matrices of their own run in one
program (a layer's output is the next one's rows, so no layer can be hoisted
or shared) and the time is the best of four batches of ``--calls`` calls,
divided by calls and layers. For each row count: every row live, and the live
share the serving cells run (12 of 64, 16 of 32, a quarter elsewhere); a row
that is not live carries weight zero and counts in no load, as a free slot
does. Prints one JSON line a row and writes them to
``chiprun_out/reached_form_bench.jsonl``. A chip number only: on the CPU it
exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deeplearning4j_tpu.models import routed_experts  # noqa: E402
from deeplearning4j_tpu.pallas import reached_experts as kernel  # noqa: E402

WIDTHS = {"olmoe": (2048, 1024, 64, 8), "mellum2": (2304, 896, 64, 8)}
LIVE = {64: 12, 32: 16}     # the two cells' live slots a step; else a quarter


def _layer(form, x, p, live, per_token):
    """``routed_ffn``'s two scopes with the form named, not chosen."""
    held = p["w_gate"].shape[0]
    weights, experts = routed_experts.route(x, p["router"], per_token)
    weights = jnp.where(live[:, None], weights, 0.0)
    load = routed_experts._load(live, experts, held)
    stored = (p["w_gate"], p["w_up"], p["w_down"])
    if form == "dense":
        y = routed_experts._dense_experts(
            x, weights, experts, *(w.astype(x.dtype) for w in stored))
    else:
        y = kernel.reached_experts(
            x, routed_experts._combine(weights, experts, held), load, *stored)
    return y.astype(x.dtype), jnp.sum(load > 0)


def _program(form, per_token):
    def run(x, layers, live):
        reached = []
        for p in layers:
            y, r = _layer(form, x, p, live, per_token)
            # unit-scale rows again, so that every layer routes afresh
            x = (y / (jnp.std(y.astype(jnp.float32)) + 1e-6)).astype(x.dtype)
            reached.append(r)
        return x, jnp.stack(reached)
    return jax.jit(run)


def _time(fn, args, calls):
    out = jax.block_until_ready(fn(*args))      # compiles
    best = None
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(calls):
            res = fn(*args)
        jax.block_until_ready(res)
        dt = (time.perf_counter() - t0) / calls
        best = dt if best is None else min(best, dt)
    return best, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="16,32,64,128,256")
    ap.add_argument("--models", default="olmoe,mellum2")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    rows_out = []
    for model in args.models.split(","):
        d, f, e, k = WIDTHS[model]
        init = jax.jit(lambda key: routed_experts.init_experts(
            key, d, f, e, jnp.float32))
        layers = [init(jax.random.PRNGKey(i)) for i in range(args.layers)]
        expert_bytes = 3 * d * f * 4
        for n in (int(r) for r in args.rows.split(",")):
            x = jax.random.normal(jax.random.PRNGKey(n), (n, d),
                                  jnp.float32).astype(jnp.bfloat16)
            for n_live in (n, LIVE.get(n, n // 4)):
                live = np.zeros((n,), bool)
                live[np.random.default_rng(n).permutation(n)[:n_live]] = True
                live = jnp.asarray(live)
                row = {"model": model, "rows": n, "live": n_live,
                       "blocks": kernel.expert_blocks(n, d, f, jnp.float32),
                       "device": dev.device_kind}
                outs = {}
                for form in ("dense", "reached"):
                    try:
                        sec, (y, reached) = _time(
                            _program(form, k), (x, layers, live), args.calls)
                    except Exception as err:   # one form refused: go on
                        row[f"{form}_error"] = str(err).strip().splitlines()[
                            0][:300]
                        continue
                    outs[form] = y.astype(jnp.float32)
                    ms = sec * 1e3 / args.layers
                    # the dense form reads every expert held
                    cells = float(e)
                    if form == "reached":
                        cells = row["reached_a_layer"] = float(
                            jnp.mean(reached))
                    row[f"{form}_ms"] = ms
                    row[f"{form}_GBs"] = cells * expert_bytes / ms / 1e6
                if len(outs) == 2:
                    # four chained bf16 layers: a check of kind, not a limit
                    row["max_diff_of_unit_rows"] = float(jnp.max(jnp.abs(
                        outs["dense"] - outs["reached"])[live]))
                    row["reached_over_dense"] = (row["reached_ms"]
                                                 / row["dense_ms"])
                rows_out.append(row)
                print(json.dumps(row), flush=True)
    with open("chiprun_out/reached_form_bench.jsonl", "w") as fh:
        for row in rows_out:
            fh.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
