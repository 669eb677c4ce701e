"""``float8_reference.py`` for the cell whose check calls
``reference_ling.forward_tail`` (``ling-serve-reason``): the plain reference
reads its weights rounded to float8 e4m3, and the cell must then come out NOT
correct.

    python3 benchmarks/tools/float8_reference_ling.py --workload ling-serve-reason --seed 7 --seconds 20 --trace 0

The rounding and its wrapper are ``float8_reference``'s, by import.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmarks import run as harness
    from benchmarks.lib import reference_ling
    from benchmarks.tools.float8_reference import rounded

    reference_ling.forward_tail = rounded(reference_ling.forward_tail)
    print("float8_reference_ling: the reference reads weights rounded to "
          "float8_e4m3fn", flush=True)
    return harness.main()


if __name__ == "__main__":
    sys.exit(main())
