"""Time ``pallas/delta_step.py`` against ``kda.kda_step`` on the attached TPU.

    chiprun -- python3 scripts/delta_step_bench.py [--groups 8,16,32] [--live 20,29,64]

One layer's state at the two serving cells' sizes (64 slots x 32 heads x
[128, 128] float32), a decay a head and a decay a channel. Each form runs
``--steps`` positions inside one jitted ``fori_loop`` over a donated state (so
the kernel's alias holds as it does in the decode program) and the time is
the best of three such calls over the steps. Prints one JSON line a row and
writes them to ``chiprun_out/delta_step_bench.jsonl``. A chip number only:
on the CPU it exits non-zero.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from deeplearning4j_tpu.models import kda  # noqa: E402
from deeplearning4j_tpu.pallas.delta_step import delta_step  # noqa: E402

HBM_GBS = 819.0      # TPU v5e, Google Cloud documentation


def _operands(slots, heads, dk, dv, per_channel, n_live, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = kda.l2norm(jax.random.normal(ks[0], (slots, heads, dk))) * dk ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (slots, heads, dk)))
    v = jax.random.normal(ks[2], (slots, heads, dv))
    g = -3.0 * jax.random.uniform(
        ks[3], (slots, heads, dk if per_channel else 1))
    beta = jax.random.uniform(ks[4], (slots, heads))
    state = jax.random.normal(ks[5], (slots, heads, dk, dv))
    live = np.zeros((slots,), bool)
    live[np.random.default_rng(seed).permutation(slots)[:n_live]] = True
    return q, k, v, g, beta, state, jnp.asarray(live)


def _plain(q, k, v, g, beta, state, live):
    g, beta = kda.mask_dead(g[:, None], beta[:, None], live[:, None])
    return kda.kda_step(q, k, v, g[:, 0], beta[:, 0], state)


def _loop(step, steps):
    def run(q, k, v, g, beta, state, live):
        def body(_, carry):
            acc, s = carry
            o, s = step(q, k, v, g, beta, s, live)
            return acc + o, s
        return lax.fori_loop(0, steps, body, (jnp.zeros_like(v), state))
    return jax.jit(run, donate_argnums=(5,))


def _time(fn, args, steps):
    best = None
    state = jnp.copy(args[5])   # the loop donates its state
    for _ in range(4):      # the first call compiles
        t0 = time.perf_counter()
        acc, state = jax.block_until_ready(
            fn(*args[:5], state, args[6]))
        dt = (time.perf_counter() - t0) / steps
        best = dt if best is None else min(best, dt)
    return best, acc, state


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", default="8,16,32")
    ap.add_argument("--live", default="20,29,64")
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    rows = []
    for per_channel in (False, True):
        for n_live in (int(x) for x in args.live.split(",")):
            ops = _operands(args.slots, args.heads, args.dim, args.dim,
                            per_channel, n_live)
            # one step of each form on the same operands: the kernel's
            # distance from the plain form, and a dead slot's bits
            want_o, want_s = jax.jit(_plain)(*ops)
            forms = [("kda_step", _plain)] + [
                (f"kernel_g{grp}", functools.partial(delta_step, heads=grp))
                for grp in (int(x) for x in args.groups.split(","))]
            for name, step in forms:
                row = {"form": name, "per_channel": per_channel,
                       "live": n_live, "device": dev.device_kind}
                try:
                    got_o, got_s = jax.jit(step)(*ops)
                    live = np.asarray(ops[6])
                    row["o_err"] = float(jnp.max(jnp.abs(
                        got_o - want_o)[live])) if n_live else 0.0
                    row["s_err"] = float(jnp.max(jnp.abs(got_s - want_s)))
                    row["dead_bits"] = bool(jnp.all(
                        (got_s == ops[5])[~live])) if n_live < len(
                            live) else True
                    sec, _, _ = _time(_loop(step, args.steps),
                                      list(ops), args.steps)
                    moved = 2 * n_live * args.heads * args.dim * args.dim * 4
                    row["ms"] = sec * 1e3
                    row["live_state_GBs"] = moved / sec / 1e9
                    row["roofline_pct"] = 100 * moved / sec / 1e9 / HBM_GBS
                except Exception as e:    # one form refused: go on
                    row["error"] = str(e).strip().splitlines()[0][:300]
                rows.append(row)
                print(json.dumps(row), flush=True)
    with open("chiprun_out/delta_step_bench.jsonl", "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
