"""The controls of the cell whose check calls
``reference_gigachat_mtp.forward_tail`` (``gigachat-serve-assist``), each of
which must come out NOT correct, and the one reading that is no control:

    python3 benchmarks/tools/float8_reference_mtp.py float8 --workload gigachat-serve-assist --seed 7 --seconds 20 --trace 0
    python3 benchmarks/tools/float8_reference_mtp.py module_off --workload gigachat-serve-assist --seed 7 --seconds 20 --trace 0
    python3 benchmarks/tools/float8_reference_mtp.py plain --workload gigachat-serve-assist --seed 7 --seconds 51 --trace 0

``float8``: the plain reference reads its weights rounded to float8 e4m3, the
precision below bf16 (the rounding and its wrapper are ``float8_reference``'s,
by import). ``module_off``: the reference zeroes the hidden-state half of the
module's input; the served drafts then miss the reference module's argmax, and
if that passed the check would not see the module. ``plain``: the PROGRAM is
built without its module (``TransformerLM(mtp=None)`` on the same weights) and
serves the same trace a token a step, with no check: its ``serve_tpot_p50_ms``
against the cell's own is what a round costs over a step, ``a* = round_ms /
plain_step_ms - 1``, the acceptance above which speculation pays (PERF.md
section 6). The other arguments are ``benchmarks/run.py``'s.
"""
import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    control = sys.argv.pop(1) if len(sys.argv) > 1 else ""
    if control not in ("float8", "module_off", "plain"):
        raise SystemExit("usage: float8_reference_mtp.py float8|module_off|"
                         "plain <benchmarks/run.py's arguments>")
    from benchmarks import run as harness
    from benchmarks.lib import reference_gigachat_mtp as ref

    if control == "float8":
        from benchmarks.tools.float8_reference import rounded

        ref.forward_tail = rounded(ref.forward_tail)
        print("float8_reference_mtp: the reference reads weights rounded to "
              "float8_e4m3fn", flush=True)
    elif control == "module_off":
        ref.forward_tail = functools.partial(ref.forward_tail,
                                             module_off=True)
        print("float8_reference_mtp: the reference's module reads no hidden "
              "state (module off)", flush=True)
    else:
        load = harness._load_json

        def without_module(path):
            got = load(path)
            if path.endswith(os.path.join("workloads",
                                          "gigachat-serve-assist.json")):
                got["module"] = False
            return got

        harness._load_json = without_module
        print("float8_reference_mtp: the program serves without its module, "
              "a token a step; nothing is checked", flush=True)
    return harness.main()


if __name__ == "__main__":
    sys.exit(main())
