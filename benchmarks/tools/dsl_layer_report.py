"""Which layer of my network is slow: the device time a step of the config
DSL's chunk program (``jit_run``) by the names the user gave the layers and
vertices, forward and backward apart, with the layer's kind
(``deeplearning4j_tpu/scopes.py``: ``dsl.conv``, ``dsl.norm``, ...) beside it,
by hand. Each op once (``layer_metrics/_dsl_scopes.py``), so the rows add up
to the time the program's ops ran; a row with no name is what ran outside every
layer (``dsl.update``, ``dsl.data``, ``unscoped``, ...). ``--group <regex>``
adds a table by the regex's first group (default: the name up to its first
``b`` or ``_``, ResNet-18's stages ``stem``, ``s0`` .. ``s3``); ``--layer
<name>`` lists the largest ops of one layer (``--layer ""``: of no layer);
``--ops <regex>`` keeps only the ops whose label matches (``'f32\\[\\d+\\]$'``:
the fusions whose result is one float32 a channel), the shares still of the
whole step.
Works on any ``jax.profiler`` trace of a process that ran ``fit_epochs`` on a
TPU, taken from a cold compile cache (the cache's key leaves names out: an
executable compiled before the names were there carries none).

    python3 benchmarks/tools/dsl_layer_report.py <file.xplane.pb | trace dir> [--layer s0b0_c1] [--top 12] [--group '^(stem|s\\d|gap|out)'] [--ops 'f32\\[\\d+\\]$']
"""

from __future__ import annotations

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.layer_metrics import _dsl_scopes  # noqa: E402
from benchmarks.lib import xplane, xplane_meta  # noqa: E402
from benchmarks.lib import program_spans  # noqa: E402


def _table(rows, total, title):
    """``rows``: ``{(name, kind): {"forward": ms, "backward": ms}}``."""
    print(f"\n{title:<24} {'kind':<12} {'forward':>9} {'backward':>9} "
          f"{'both':>9}  share")
    for (name, kind), ms in sorted(rows.items(),
                                   key=lambda kv: -sum(kv[1].values())):
        f, b = ms.get("forward", 0.0), ms.get("backward", 0.0)
        print(f"{name or '-':<24} {kind:<12} {f:9.3f} {b:9.3f} {f + b:9.3f}  "
              f"{100 * (f + b) / total:5.1f} %")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--layer", default=None)
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--group", default=r"^([^_b]+)")
    ap.add_argument("--ops", default=None)
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") \
        else xplane.find_xplane(args.trace)
    if not path:
        print(f"no *.xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    trace = xplane.load_xplane(path)
    # the program's spans of THIS file, whatever lies under .bench_out
    trace.host = sorted(program_spans.read_host_events(path),
                        key=lambda e: (e.start, -e.dur))
    tf_op = {dev: {op: str(st["tf_op"]) for op, st in ops.items()}
             for dev, ops in xplane_meta.op_metadata(path, ("tf_op",)).items()}
    ops = _dsl_scopes.step_ms(trace, None, tf_op, detail=True)
    if not ops:
        print("no chunk program with dsl.* names in this trace (an older "
              "checkout, or an executable from a warm compile cache)",
              file=sys.stderr)
        return 1
    total = sum(ops.values())
    print(f"{path}: {len(trace.devices)} device(s), "
          f"{_dsl_scopes.chunk(trace)[1]:g} steps an execution, "
          f"{_dsl_scopes.step_device_ms(trace):.4f} ms a step on the device, "
          f"{total:.4f} ms a step in ops")
    if args.ops:
        ops = {at: ms for at, ms in ops.items() if re.search(args.ops, at[1])}
        print(f"ops matching {args.ops!r}: {sum(ops.values()):.4f} ms a step")
    layers, kinds, groups = {}, {}, {}
    rx = re.compile(args.group)
    for (name, kind, way), ms in _dsl_scopes.by_layer(ops).items():
        m = rx.search(name)
        for rows, at in ((layers, (name, kind)), (kinds, ("", kind)),
                         (groups, (m.group(1) if m else "", ""))):
            got = rows.setdefault(at, {})
            got[way] = got.get(way, 0.0) + ms
    _table(kinds, total, "by kind")
    _table(groups, total, "by group")
    _table(layers, total, "by layer")
    if args.layer is not None:
        mine = sorted(((ms, at) for at, ms in ops.items()
                       if _dsl_scopes.layer_of(at[2])[0] == args.layer),
                      reverse=True)[:args.top]
        print(f"\nthe largest ops of {args.layer or 'no layer'}:")
        for ms, (label, op, scope) in mine:
            print(f"  [{label}] {op}: {ms:.4f} ms  {100 * ms / total:.1f} %  "
                  f"{scope[:160]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
