"""Shared by the routed-experts metrics: device time of the ops the program
put under its ``moe.route`` and ``moe.experts`` scopes, inside the decode
program's executions or inside the prefill programs'.

A ``jax.named_scope`` reaches the trace as the op's ``tf_op`` stat
(``jit(...)/.../moe.experts/dot_general``), which the profile keeps once per
kind of op, where ``ProfileData`` does not show it: ``lib/xplane_meta.py``
reads it from the trace file and it is joined to the events by op name. The
workload file's ``moe_scopes`` is the regular expression that finds the
scopes. Which executions are decode and which prefill is ``_serve``'s answer
(the program whose runs in the trace number the server's steps); an op
belongs to the execution whose interval holds its start. Nested ops count
once (self time). Every function returns ``None`` where there is nothing to
read: no trace file, a program without the scopes (before PR 25), a cell
without the key.
"""

import json
import os
import re

from benchmarks.layer_metrics import _serve
from benchmarks.lib import xplane, xplane_meta

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def trace_scopes(ctx):
    """``{device: {op name: tf_op}}`` from the traced run's own file (the
    harness writes it under ``.bench_out/trace-<cell>``), or ``None``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    cell = ctx["cell"]
    name = next((w["name"] for w in cells
                 if (w["config"], w["traffic"]) == (cell["config"],
                                                    cell["traffic_name"])),
                None)
    path = name and xplane.find_xplane(
        os.path.join(ROOT, ".bench_out", "trace-" + name))
    if not path:
        return None
    return {dev: {op: str(stats["tf_op"]) for op, stats in ops.items()}
            for dev, ops in xplane_meta.op_metadata(path, ("tf_op",)).items()}


def scoped_seconds(trace, counters, ctx, scopes=None):
    """``{"decode": (seconds, executions), "prefill": (seconds,
    executions)}`` of the ops under the cell's ``moe_scopes``; ``scopes``
    is ``trace_scopes(ctx)`` unless a test hands one in."""
    pattern = ctx["cell"].get("moe_scopes")
    if not pattern or not trace.devices:
        return None
    key = _serve._decode_id(trace, counters, ctx)
    scopes = trace_scopes(ctx) if scopes is None else scopes
    if key is None or not scopes:
        return None
    rx = re.compile(pattern)
    prefill_keys = set(_serve._runs(
        trace, ctx["cell"]["prefill_program"]["module"])) - {key}
    lo, hi = trace.window()
    out = {"decode": [0.0, 0], "prefill": [0.0, 0]}
    hit = False
    for dev, d in trace.devices.items():
        tf_op = scopes.get(dev, {})
        ops = [e for e in d.ops if rx.search(tf_op.get(e.name, ""))]
        hit |= bool(ops)
        for kind, keys in (("decode", {key}), ("prefill", prefill_keys)):
            runs = [(e.start, e.end) for e in d.modules
                    if lo <= e.start < hi and e.name.strip() in keys]
            inside = [e for e in ops
                      if any(a <= e.start < b for a, b in runs)]
            out[kind][0] += sum(
                ns for _, ns in xplane.self_times(inside)) / 1e9
            out[kind][1] += len(runs)
    return {k: tuple(v) for k, v in out.items()} if hit else None
