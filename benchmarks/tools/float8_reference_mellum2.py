"""The controls of the cell whose check calls
``reference_mellum2.forward_tail`` (``mellum2-serve-mixed``), each of which
must come out NOT correct:

    python3 benchmarks/tools/float8_reference_mellum2.py float8 --workload mellum2-serve-mixed --seed 7 --seconds 20 --trace 0
    python3 benchmarks/tools/float8_reference_mellum2.py no_window ...
    python3 benchmarks/tools/float8_reference_mellum2.py one_rope ...
    python3 benchmarks/tools/float8_reference_mellum2.py stale_ring ...

``float8``: the plain reference reads its weights rounded to float8 e4m3, the
precision below bf16 (the rounding and its wrapper are ``float8_reference``'s,
by import). ``no_window``: the reference's sliding layers see their whole
prefix; ``one_rope``: its full layer turns with the sliding layers' table;
``stale_ring``: its sliding layers see ``t - 2047 .. t``, what a ring that
masks one lap late would compute. If any passed, the check would not see the
mechanism. The other arguments are ``benchmarks/run.py``'s.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = ("float8", "no_window", "one_rope", "stale_ring")


def main() -> int:
    control = sys.argv.pop(1) if len(sys.argv) > 1 else ""
    if control not in CONTROLS:
        raise SystemExit("usage: float8_reference_mellum2.py "
                         + "|".join(CONTROLS)
                         + " <benchmarks/run.py's arguments>")
    from benchmarks import run as harness
    from benchmarks.lib import reference_mellum2 as ref

    plain = ref.forward_tail
    if control == "float8":
        from benchmarks.tools.float8_reference import rounded

        ref.forward_tail = rounded(plain)
        print("float8_reference_mellum2: the reference reads weights rounded "
              "to float8_e4m3fn", flush=True)
    else:
        def switched(params, tokens, cfg, *args, **kw):
            return plain(params, tokens, {**cfg, "control": control}, *args,
                         **kw)

        ref.forward_tail = switched
        print(f"float8_reference_mellum2: the reference runs with {control}",
              flush=True)
    return harness.main()


if __name__ == "__main__":
    sys.exit(main())
