"""The controls of the cell whose check calls
``reference_glm53.forward_tail`` (``glm53-serve-agent``), each of which must
come out NOT correct:

    python3 benchmarks/tools/float8_reference_glm53.py float8 --workload glm53-serve-agent --seed 7 --seconds 20 --trace 0
    python3 benchmarks/tools/float8_reference_glm53.py plain --workload glm53-serve-agent --seed 7 --seconds 20 --trace 0
    python3 benchmarks/tools/float8_reference_glm53.py recent --workload glm53-serve-agent --seed 7 --seconds 20 --trace 0

``float8``: the plain reference reads its weights rounded to float8 e4m3, the
precision below bf16 (the rounding and its wrapper are ``float8_reference``'s,
by import). ``plain``: the reference replaces every hyper-connection map by
the plain residual's (``H_res = I``, ``H_pre = 1 / n``, ``H_post = 1``: the
streams stay copies of one plain residual); if that passed, the check would
not see the residual path. ``recent``: the reference replaces the selection
by the most recent ``index_topk`` positions; if that passed, the check would
not see the indexer. The other arguments are ``benchmarks/run.py``'s.
"""
import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    control = sys.argv.pop(1) if len(sys.argv) > 1 else ""
    if control not in ("float8", "plain", "recent"):
        raise SystemExit("usage: float8_reference_glm53.py float8|plain|"
                         "recent <benchmarks/run.py's arguments>")
    from benchmarks import run as harness
    from benchmarks.lib import reference_glm53 as ref

    if control == "float8":
        from benchmarks.tools.float8_reference import rounded

        ref.forward_tail = rounded(ref.forward_tail)
        print("float8_reference_glm53: the reference reads weights rounded "
              "to float8_e4m3fn", flush=True)
    else:
        ref.forward_tail = functools.partial(ref.forward_tail,
                                             **{control: True})
        print("float8_reference_glm53: the reference "
              + ("keeps the plain residual (every map the identity's)"
                 if control == "plain" else
                 "attends the most recent index_topk positions (selection "
                 "off)"), flush=True)
    return harness.main()


if __name__ == "__main__":
    sys.exit(main())
