"""The controls of the cell whose check calls
``reference_brumby.forward_tail`` (``brumby-serve-continue``), each of which
must come out NOT correct:

    python3 benchmarks/tools/float8_reference_brumby.py float8 --workload brumby-serve-continue --seed 7 --seconds 20 --trace 0
    ... no_decay | p4 | softmax | bf16_state ...

``float8``: the plain reference reads its weights rounded to float8 e4m3, the
precision below bf16 (the rounding and its wrapper are ``float8_reference``'s,
by import). ``no_decay``: the reference's gates are all 1, a sum that never
forgets; ``p4``: its scores are raised to the fourth power; ``softmax``: it
takes ``exp`` of a score in place of the power. ``bf16_state``: the *program*
runs with its state rounded to bf16 after every prefill and every decode step
(``models/ret.py``'s two recurrences wrapped here; every slot's state is
passed over once more a step, so the run is slow and only its ``correct``
counts). If one passed, the check would not see that part of the mechanism.
The other arguments are ``benchmarks/run.py``'s.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = ("float8", "no_decay", "p4", "softmax", "bf16_state")


def _bf16_state():
    """``ret.ret_scan`` and ``ret.retention_step`` handing on a state rounded
    to bf16 (kept in float32 arrays: the pool's layout is the program's)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import ret

    def rounded(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    scan, step = ret.ret_scan, ret.retention_step

    def ret_scan(*args, **kw):
        o, state = scan(*args, **kw)
        return o, jax.tree_util.tree_map(rounded, state)

    def retention_step(*args, **kw):
        o, s, z = step(*args, **kw)
        return o, rounded(s), rounded(z)

    ret.ret_scan, ret.retention_step = ret_scan, retention_step


def main() -> int:
    control = sys.argv.pop(1) if len(sys.argv) > 1 else ""
    if control not in CONTROLS:
        raise SystemExit("usage: float8_reference_brumby.py "
                         + "|".join(CONTROLS)
                         + " <benchmarks/run.py's arguments>")
    from benchmarks import run as harness
    from benchmarks.lib import reference_brumby as ref

    plain = ref.forward_tail
    if control == "float8":
        from benchmarks.tools.float8_reference import rounded

        ref.forward_tail = rounded(plain)
        print("float8_reference_brumby: the reference reads weights rounded "
              "to float8_e4m3fn", flush=True)
    elif control == "bf16_state":
        _bf16_state()
        print("float8_reference_brumby: the program's state is rounded to "
              "bf16 after every prefill and decode step", flush=True)
    else:
        def switched(params, tokens, cfg, *args, **kw):
            return plain(params, tokens, {**cfg, "control": control}, *args,
                         **kw)

        ref.forward_tail = switched
        print(f"float8_reference_brumby: the reference runs with {control}",
              flush=True)
    return harness.main()


if __name__ == "__main__":
    sys.exit(main())
