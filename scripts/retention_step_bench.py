"""Time ``pallas/retention_step.py`` against ``ret.ret_step``, and the chunked
``ret.ret_scan``, on the attached TPU.

    chiprun -- python3 scripts/retention_step_bench.py [--live 8,20,32] [--prompt 4096]

One layer's state at ``brumby-serve-continue``'s sizes (32 slots x 8 kv heads
x [8,704, 128] float32, 5 queries a kv head). Each step form runs ``--steps``
positions inside one jitted ``fori_loop`` over a donated state (so the
kernel's alias holds as it does in the decode program) and the time is the
best of three such calls over the steps; the plain form runs at ``--plain``
slots only (it expands ``phi`` of every row in HBM). The scan runs one prompt
of ``--prompt`` positions in bf16 operands, as a prefill does. Prints one
JSON line a row and writes them to ``chiprun_out/retention_step_bench.jsonl``.
A chip number only: on the CPU it exits non-zero.
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
from jax import lax  # noqa: E402

from deeplearning4j_tpu.models import ret  # noqa: E402
from deeplearning4j_tpu.pallas.retention_step import retention_step  # noqa: E402

HBM_GBS = 819.0      # TPU v5e, Google Cloud documentation
MXU_TFLOPS = 197.0


def _operands(slots, hkv, rep, d, n_live, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    scale = d ** -0.25
    q = jax.random.normal(ks[0], (slots, hkv * rep, d)) * scale
    k = jax.random.normal(ks[1], (slots, hkv, d)) * scale
    v = jax.random.normal(ks[2], (slots, hkv, d))
    lg = -0.3 * jax.random.uniform(ks[3], (slots, hkv))
    s = jax.random.normal(ks[4], (slots, hkv, ret.state_rows(d), d))
    z = jnp.abs(jax.random.normal(ks[5], (slots, hkv, d, d))) + 1.0
    live = np.zeros((slots,), bool)
    live[np.random.default_rng(seed).permutation(slots)[:n_live]] = True
    return q, k, v, lg, s, z, jnp.asarray(live)


def _plain(q, k, v, lg, s, z, live):
    lg = jnp.where(live[:, None], lg, 0.0)
    k = jnp.where(live[:, None, None], k, 0.0)
    o, (s, z) = ret.ret_step(q, k, v, lg, (s, z))
    return jnp.where(live[:, None, None], o, 0.0), s, z


def _kernel(q, k, v, lg, s, z, live):
    return retention_step(q, k, v, lg, s, z, live, eps=ret.EPS)


def _loop(step, steps):
    def run(q, k, v, lg, s, z, live):
        def body(_, carry):
            acc, s, z = carry
            o, s, z = step(q, k, v, lg, s, z, live)
            return acc + o, s, z
        return lax.fori_loop(0, steps, body, (jnp.zeros_like(q), s, z))
    return jax.jit(run, donate_argnums=(4, 5))


def _time(fn, args, steps):
    best = None
    s, z = jnp.copy(args[4]), jnp.copy(args[5])     # the loop donates them
    for _ in range(4):      # the first call compiles
        t0 = time.perf_counter()
        _, s, z = jax.block_until_ready(fn(*args[:4], s, z, args[6]))
        dt = (time.perf_counter() - t0) / steps
        best = dt if best is None else min(best, dt)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", default="0,1,8,20,32")
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--plain", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--rep", type=int, default=5)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--prompt", default="1024,4096")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    rows = []
    hkv, rep, d = args.kv_heads, args.rep, args.dim
    head_bytes = (ret.state_rows(d) * d + d * d) * 4

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    # the kernel against the plain form, on the same operands
    ops = _operands(args.plain, hkv, rep, d, max(1, args.plain // 2))
    want = jax.jit(_plain)(*ops)
    got = jax.jit(_kernel)(*ops)
    live = np.asarray(ops[6])
    row = {"form": "agree", "slots": args.plain, "device": dev.device_kind,
           "o_rel_err": float(jnp.max(jnp.abs(got[0] - want[0])[live])
                              / jnp.max(jnp.abs(want[0]))),
           "s_err": float(jnp.max(jnp.abs(got[1] - want[1]))),
           "z_err": float(jnp.max(jnp.abs(got[2] - want[2]))),
           "dead_bits": bool(jnp.all((got[1] == ops[4])[~live])
                             and jnp.all((got[2] == ops[5])[~live]))}
    sec = _time(_loop(_plain, args.steps), list(ops), args.steps)
    row["plain_ms"] = sec * 1e3
    emit(row)
    for n_live in (int(x) for x in args.live.split(",")):
        ops = _operands(args.slots, hkv, rep, d, n_live)
        row = {"form": "kernel", "slots": args.slots, "live": n_live,
               "device": dev.device_kind}
        try:
            sec = _time(_loop(_kernel, args.steps), list(ops), args.steps)
            moved = 2 * n_live * hkv * head_bytes
            row.update(ms=sec * 1e3, live_state_GBs=moved / sec / 1e9,
                       roofline_pct=100 * moved / sec / 1e9 / HBM_GBS)
        except Exception as e:    # one form refused: go on
            row["error"] = str(e).strip().splitlines()[0][:300]
        emit(row)
    for t in (int(x) for x in args.prompt.split(",")):
        ks = jax.random.split(jax.random.PRNGKey(t), 4)
        q = jax.random.normal(ks[0], (1, t, hkv * rep, d)) * d ** -0.25
        k = jax.random.normal(ks[1], (1, t, hkv, d)) * d ** -0.25
        v = jax.random.normal(ks[2], (1, t, hkv, d))
        lg = -0.3 * jax.random.uniform(ks[3], (1, t, hkv))
        state = ret._zero_state(1, hkv, d)
        scan = jax.jit(lambda *a: ret.ret_scan(*a, jnp.bfloat16))
        row = {"form": "scan", "prompt": t, "chunk": ret.CHUNK,
               "device": dev.device_kind}
        try:
            best = None
            for _ in range(4):
                t0 = time.perf_counter()
                jax.block_until_ready(scan(q, k, v, lg, state))
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            flops = t * (2.0 * (hkv + hkv * rep) * ret.state_rows(d) * d
                         + hkv * rep * 4.0 * ret.CHUNK * d)
            row.update(ms=best * 1e3, us_per_token=best * 1e6 / t,
                       mxu_pct=100 * flops / best / 1e12 / MXU_TFLOPS)
        except Exception as e:
            row["error"] = str(e).strip().splitlines()[0][:300]
        emit(row)
    with open("chiprun_out/retention_step_bench.jsonl", "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
