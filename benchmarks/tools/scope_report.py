"""Device time by the program's own scopes in one trace, by hand: for every
program that ran (name and number, ``jit__unknown(7)``) its executions, the
mean device time of one, and the time an execution's ops spent under each
name of the scope vocabulary (``deeplearning4j_tpu/scopes.py``), in Pallas
kernels (``mosaic``) and under no scope -- each op once, so the rows of a
program add up to the time its ops ran (``layer_metrics/_scopes.py``, which
the ``*_ms_per_step`` / ``*_ms_per_decode_step`` scope metrics read the same
way). Below each program, the largest ops under ``--ops`` labels (default
``unscoped``: what a scope should still take in; ``all``: whatever the
label, which shows where XLA put a fusion that straddles two scopes). Works on any
``jax.profiler`` trace of a training or serving process on a TPU.

    python3 benchmarks/tools/scope_report.py <file.xplane.pb | trace dir> [--min-runs 2] [--ops unscoped,lm.head] [--top 8]
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.layer_metrics import _scopes  # noqa: E402
from benchmarks.lib import xplane, xplane_meta  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--min-runs", type=int, default=2)
    ap.add_argument("--ops", default="unscoped")
    ap.add_argument("--top", type=int, default=8)
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") \
        else xplane.find_xplane(args.trace)
    if not path:
        print(f"no *.xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    names = _scopes.vocabulary()
    if names is None:
        print("this checkout has no deeplearning4j_tpu/scopes.py",
              file=sys.stderr)
        return 1
    trace = xplane.load_xplane(path)
    tf_op = {dev: {op: str(st["tf_op"]) for op, st in ops.items()}
             for dev, ops in xplane_meta.op_metadata(path, ("tf_op",)).items()}
    found = _scopes.by_scope(trace, tf_op, names, detail=True)
    shown = set(args.ops.split(","))
    print(f"{path}: {os.path.getsize(path)} bytes, "
          f"{len(trace.devices)} device(s)")
    for key, p in sorted(found.items(), key=lambda kv: -(
            kv[1]["runs"] * kv[1]["device_ms"])):
        if p["runs"] < args.min_runs:
            continue
        ops_ms = sum(p["ms"].values())
        print(f"\n{key}: {p['runs']} executions, {p['device_ms']:.4f} ms "
              f"each on the device, {ops_ms:.4f} ms in ops")
        for name, ms in sorted(p["ms"].items(), key=lambda kv: -kv[1]):
            print(f"  {name:<12} {ms:9.4f} ms  {100 * ms / ops_ms:5.1f} %")
        ops = sorted(((ms, at) for at, ms in p["ops"].items()
                      if at[0] in shown or "all" in shown),
                     reverse=True)[:args.top]
        for ms, (got, op, scope) in ops:
            print(f"    [{got}] {op}: {ms:.4f} ms  "
                  f"{100 * ms / ops_ms:.1f} %  {scope[:140]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
