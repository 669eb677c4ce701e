"""Shared by the scope metrics: a program's device time, whole, split by the
program's own ``jax.named_scope``s.

Every op that starts inside an execution of one program -- the decode program
(``_serve._decode_id``) or the training step (the cell's ``step_program``) --
gets one label, and its self time (``xplane.self_times``: a ``while`` less its
body) goes to that label once:

- ``mosaic`` for a Pallas kernel (``custom_call_target="tpu_custom_call"``),
  whatever scope it sits under: ``decode_attn_ms_per_step``,
  ``flash_ms_per_step`` and the ``moe_*`` readers already count those;
- else the last name of the program's vocabulary (``deeplearning4j_tpu/
  scopes.py``) in the op's ``tf_op`` (``jit(step)/transpose(jvp(ffn.dense))/
  dot_general`` is ``ffn.dense``, forward or backward; under ``mla.attend/
  attn.core`` the inner one);
- else ``unscoped``.

So the labels of a program add up to the time its ops ran, each op once. The
``tf_op`` is joined by op name from the trace file (``_moe.trace_scopes``).
Every function returns ``None`` where there is nothing to read: no trace file,
a program that is not told apart, a program from before the vocabulary (no
``deeplearning4j_tpu.scopes``), or one with no op under any of ``NEW``.
"""

import bisect
import re

from benchmarks.layer_metrics import _moe, _serve
from benchmarks.lib import xplane

MOSAIC = 'custom_call_target="tpu_custom_call"'
# the scopes PR 33 brought: a program with none of them was lowered before it
NEW = ("lm.embed", "attn.proj", "attn.core", "kv.write", "ffn.dense",
       "lm.head", "opt.cast", "opt.update")


def vocabulary():
    """The program's scope names, or ``None`` for a program without the
    list (the benchmark's files laid over an older checkout)."""
    try:
        from deeplearning4j_tpu.scopes import SCOPES
    except ImportError:
        return None
    return tuple(SCOPES)


def name_pattern(names):
    """A regular expression that finds the scope ``names`` in a name-stack
    path, each as a whole component (``jvp(ffn.dense)/dot_general``)."""
    return re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(
        re.escape(n) for n in sorted(names, key=len, reverse=True)))


def labeller(names):
    """``(op name, tf_op) -> label`` as the module's docstring says."""
    rx = name_pattern(names)

    def label(op, tf_op):
        if MOSAIC in op:
            return "mosaic"
        found = rx.findall(tf_op or "")
        return found[-1] if found else "unscoped"

    return label


def by_scope(trace, tf_op, names, keys=None, detail=False):
    """``{program: {"runs": n, "device_ms": mean ms of an execution,
    "ms": {label: ms an execution}}}`` for the programs ``keys`` (name and
    number, ``jit_step(12)``; default: every program in the window), over all
    devices. ``tf_op`` is ``{device: {op name: tf_op}}``. With ``detail``
    also ``"ops": {(label, op label, tf_op): ms an execution}``."""
    label = labeller(names)
    lo, hi = trace.window()
    out = {}
    for dev, d in trace.devices.items():
        runs = sorted((e.start, e.end, e.name.strip()) for e in d.modules
                      if lo <= e.start < hi
                      and (keys is None or e.name.strip() in keys))
        starts = [r[0] for r in runs]
        for a, b, key in runs:
            got = out.setdefault(key, {"runs": 0, "device_ns": 0.0,
                                       "ns": {}, "ops": {}})
            got["runs"] += 1
            got["device_ns"] += b - a

        def program_of(e):
            i = bisect.bisect_right(starts, e.start) - 1
            return runs[i][2] if i >= 0 and e.start < runs[i][1] else None

        scopes = tf_op.get(dev, {})
        inside = [e for e in d.ops if program_of(e) is not None]
        for e, self_ns in xplane.self_times(inside):
            got = out[program_of(e)]
            scope = scopes.get(e.name, "")
            name = label(e.name, scope)
            got["ns"][name] = got["ns"].get(name, 0.0) + self_ns
            if detail:
                at = (name, xplane.op_label(e), scope)
                got["ops"][at] = got["ops"].get(at, 0.0) + self_ns
    found = {}
    for key, got in out.items():
        n = 1e6 * got["runs"]
        found[key] = {"runs": got["runs"], "device_ms": got["device_ns"] / n,
                      "ms": {k: v / n for k, v in got["ns"].items()}}
        if detail:
            found[key]["ops"] = {k: v / n for k, v in got["ops"].items()}
    return found


_read = {}      # (trace, programs) -> labels: a cell's readers share one pass


def _program_ms(trace, ctx, keys, scopes):
    """The labels of the programs ``keys`` taken together, in ms an
    execution, or ``None``. ``scopes`` is ``_moe.trace_scopes(ctx)`` unless
    a test hands one in."""
    names = vocabulary()
    if not keys or not names or not trace.devices:
        return None
    at = (id(trace), tuple(sorted(keys)))
    if scopes is None and at in _read:
        return _read[at]
    tf_op = _moe.trace_scopes(ctx) if scopes is None else scopes
    ms = None
    found = by_scope(trace, tf_op, names, keys) if tf_op else {}
    runs = sum(p["runs"] for p in found.values())
    if runs:
        ms = {}
        for p in found.values():
            for k, v in p["ms"].items():
                ms[k] = ms.get(k, 0.0) + v * p["runs"] / runs
        if not any(n in ms for n in NEW):
            ms = None
    if scopes is None:
        _read[at] = ms
    return ms


def decode_ms(trace, counters, ctx, scopes=None):
    """``{label: ms a decode step}`` of the decode program."""
    key = _serve._decode_id(trace, counters, ctx)
    return None if key is None else _program_ms(trace, ctx, {key}, scopes)


def step_ms(trace, counters, ctx, scopes=None):
    """``{label: ms a step}`` of the training step program."""
    pat = ctx["cell"].get("step_program")
    if not pat:
        return None
    rx = re.compile(pat)
    keys = {k for k in xplane.module_times(trace, by_id=True)
            if rx.search(xplane.module_name(k))}
    return _program_ms(trace, ctx, keys, scopes)


def of(ms, *labels):
    """The time under ``labels`` together, or ``None`` where the program has
    nothing under any of them."""
    if not ms or not any(k in ms for k in labels):
        return None
    return sum(ms.get(k, 0.0) for k in labels)
