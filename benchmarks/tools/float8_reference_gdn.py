"""The controls of the cell whose check calls
``reference_qwen3_next.forward_tail`` (``qwen3next-serve-longctx``), each of
which must come out NOT correct:

    python3 benchmarks/tools/float8_reference_gdn.py float8 --workload qwen3next-serve-longctx --seed 7 --seconds 20 --trace 0
    python3 benchmarks/tools/float8_reference_gdn.py no_decay --workload qwen3next-serve-longctx --seed 7 --seconds 20 --trace 0
    python3 benchmarks/tools/float8_reference_gdn.py no_gate --workload qwen3next-serve-longctx --seed 7 --seconds 20 --trace 0

``float8``: the plain reference reads its weights rounded to float8 e4m3, the
precision below bf16 (the rounding and its wrapper are ``float8_reference``'s,
by import). ``no_decay``: the reference's Gated DeltaNet layers take the
scalar gate ``g`` as 0, a state that never forgets; ``no_gate``: its attention
layer leaves the output gate out. If either passed, the check would not see
the mechanism. The other arguments are ``benchmarks/run.py``'s.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    control = sys.argv.pop(1) if len(sys.argv) > 1 else ""
    if control not in ("float8", "no_decay", "no_gate"):
        raise SystemExit("usage: float8_reference_gdn.py float8|no_decay|"
                         "no_gate <benchmarks/run.py's arguments>")
    from benchmarks import run as harness
    from benchmarks.lib import reference_qwen3_next as ref

    plain = ref.forward_tail
    if control == "float8":
        from benchmarks.tools.float8_reference import rounded

        ref.forward_tail = rounded(plain)
        print("float8_reference_gdn: the reference reads weights rounded to "
              "float8_e4m3fn", flush=True)
    else:
        def switched_off(params, tokens, cfg, *args, **kw):
            return plain(params, tokens, {**cfg, "control": control}, *args,
                         **kw)

        ref.forward_tail = switched_off
        print(f"float8_reference_gdn: the reference runs with {control}",
              flush=True)
    return harness.main()


if __name__ == "__main__":
    sys.exit(main())
