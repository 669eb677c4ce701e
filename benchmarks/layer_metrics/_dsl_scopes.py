"""Shared by the ``dsl_*`` metrics: the config DSL's chunk program (``nn/
train_step.epoch_run_fn``: epochs x batches optimizer steps in one
execution) read from inside -- its device time a step, split by the
``dsl.*`` scopes of ``deeplearning4j_tpu/scopes.py`` (a layer's kind) and,
for ``tools/dsl_layer_report.py``, by the ``layer.<name>`` component that
carries the name the user gave each layer and vertex.

The chunk program is every program called ``jit_run`` (``PROGRAM``: the
DSL's cells have no ``step_program`` in their workload files). Its executions
on a device in the window must number the window's ``epoch.chunk`` spans
(``perf/epoch_cache.drive_epoch_chunks`` opens one round each launch), give
or take ``EDGE``: else another ``jit_run`` ran there and the reader says so
and reports nothing. Steps an execution are the PROGRAM's count, the
``steps`` attr of those spans, not the harness's.

The split is ``_scopes.by_scope``'s: each op once, a ``while`` less its body,
the last vocabulary name of an op's ``tf_op`` -- so the labels add up to the
time the program's ops ran. What XLA's SPMD partitioner adds (the gradient
all-reduce) goes to the ``tf_op`` it was given, or to ``unscoped``; it is also
what ``collective_pct`` counts: the two are not to be added.

Every function returns ``None`` where there is nothing to read: no trace, no
``epoch.chunk`` span (a program from before PR 23), a run count that does not
match, or a program that carries no ``dsl.*`` name (one from before PR 49, or
an executable the compile cache kept from such a checkout: the cache's key
leaves metadata out).
"""

import re
import sys

from benchmarks.layer_metrics import _moe, _program_spans, _scopes
from benchmarks.lib import xplane

PROGRAM = re.compile(r"^jit_run$")
EDGE = 1        # executions the profiler's start and stop may add or lose
PREFIX = "dsl."
LAYERS = ("dsl.pool", "dsl.dense", "dsl.embed", "dsl.recurrent", "dsl.act",
          "dsl.vertex", "dsl.layer", "dsl.loss")
# scopes.LAYER_PREFIX, spelled out: the parent of PR 49 has none to import
_LAYER_NAME = re.compile(r"(?:^|[/(;])layer\.([^/();]+)")


def chunk(trace):
    """``(keys, steps an execution, device ms an execution)`` of the chunk
    program, or ``None``."""
    launched = _program_spans.spans(trace, "epoch.chunk")
    steps = [int(float(e.stats["steps"])) for e in launched
             if "steps" in e.stats]
    if not steps or not trace.devices:
        return None
    secs = {k: v for k, v in xplane.module_times(trace, by_id=True).items()
            if PROGRAM.search(xplane.module_name(k))}
    runs = sum(len(v) for v in secs.values()) / len(trace.devices)
    if not secs or abs(runs - len(steps)) > EDGE:
        print(f"layer_metrics: {runs:g} executions of {PROGRAM.pattern} a "
              f"device for {len(steps)} epoch.chunk spans in the trace; the "
              "chunk program is not told apart", file=sys.stderr)
        return None
    total = sum(s for v in secs.values() for s in v)
    return (set(secs), sum(steps) / len(steps),
            1e3 * total / sum(len(v) for v in secs.values()))


def step_device_ms(trace):
    found = chunk(trace)
    return None if found is None else found[2] / found[1]


_read = {}      # trace -> labels: a cell's readers share one pass


def step_ms(trace, ctx, scopes=None, detail=False):
    """``{label: ms a step}`` of the chunk program (with ``detail``:
    ``{(label, op label, tf_op): ms a step}``), or ``None``. ``scopes`` is
    ``_moe.trace_scopes(ctx)`` unless a test or a tool hands one in."""
    shared = scopes is None and not detail
    if shared and id(trace) in _read:
        return _read[id(trace)]
    ms = None
    names, found = _scopes.vocabulary(), chunk(trace)
    if found and names and any(n.startswith(PREFIX) for n in names):
        tf_op = _moe.trace_scopes(ctx) if scopes is None else scopes
        keys, steps, _ = found
        got = (_scopes.by_scope(trace, tf_op, names, keys, detail=detail)
               if tf_op else {})
        runs = sum(p["runs"] for p in got.values())
        ms = {}
        for p in got.values():
            for k, v in p["ops" if detail else "ms"].items():
                ms[k] = ms.get(k, 0.0) + v * p["runs"] / runs / steps
        if not any((k[0] if detail else k).startswith(PREFIX) for k in ms):
            print(f"layer_metrics: no op of {PROGRAM.pattern} carries a "
                  f"{PREFIX}* name (an executable from a compile cache "
                  "that an older checkout filled?)", file=sys.stderr)
            ms = None
    if shared:
        _read[id(trace)] = ms
    return ms


def of(ms, *labels):
    """The time under ``labels`` together: 0 where a program that was read
    has nothing there."""
    return None if ms is None else sum(ms.get(k, 0.0) for k in labels)


def outside(ms):
    """The time under no ``dsl.*`` name: ``unscoped``, ``mosaic`` (no kernel
    sits under ``nn/`` today) and whatever else the vocabulary holds."""
    if ms is None:
        return None
    return sum(v for k, v in ms.items() if not k.startswith(PREFIX))


def layer_of(tf_op):
    """``(the user's name of the layer or vertex an op ran under, "forward"
    | "backward")``; the name is ``""`` outside every layer."""
    name = _LAYER_NAME.findall(tf_op)
    return (name[-1] if name else "",
            "backward" if "transpose(" in tf_op else "forward")


def by_layer(ops):
    """``step_ms(..., detail=True)`` grouped by the user's names:
    ``{(layer name, label, "forward" | "backward"): ms a step}``."""
    out = {}
    for (label, _, tf_op), ms in ops.items():
        name, way = layer_of(tf_op)
        out[name, label, way] = out.get((name, label, way), 0.0) + ms
    return out
