"""The controls of the cell whose check calls
``reference_glm_dsa.forward_tail`` (``glm-serve-longdoc``), each of which must
come out NOT correct:

    python3 benchmarks/tools/float8_reference_glm.py float8 --workload glm-serve-longdoc --seed 7 --seconds 20 --trace 0
    python3 benchmarks/tools/float8_reference_glm.py dense --workload glm-serve-longdoc --seed 7 --seconds 20 --trace 0
    python3 benchmarks/tools/float8_reference_glm.py recall95 --workload glm-serve-longdoc --seed 7 --seconds 20 --trace 0

``float8``: the plain reference reads its weights rounded to float8 e4m3, the
precision below bf16 (the rounding and its wrapper are ``float8_reference``'s,
by import). ``dense``: the reference switches the selection off and every query
attends every position before it; if that passed, the check would not see the
mechanism. ``recall95``: the PROGRAM's decode steps select at recall 0.95 (the
weakest twentieth of each query's picks gives way to the best keys that were
not picked, what an approximate top-k does), against the plain reference: the
check has to see that through ``select_overlap``, whatever the swapped keys
score. The other arguments are ``benchmarks/run.py``'s.
"""
import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    control = sys.argv.pop(1) if len(sys.argv) > 1 else ""
    if control not in ("float8", "dense", "recall95"):
        raise SystemExit("usage: float8_reference_glm.py float8|dense|"
                         "recall95 <benchmarks/run.py's arguments>")
    from benchmarks import run as harness
    from benchmarks.lib import reference_glm_dsa as ref

    if control == "float8":
        from benchmarks.tools.float8_reference import rounded

        ref.forward_tail = rounded(ref.forward_tail)
        print("float8_reference_glm: the reference reads weights rounded to "
              "float8_e4m3fn", flush=True)
    elif control == "dense":
        ref.forward_tail = functools.partial(ref.forward_tail, dense=True)
        print("float8_reference_glm: the reference attends every position "
              "(selection off)", flush=True)
    else:
        from deeplearning4j_tpu.models import dsa

        dsa.select = at_recall(dsa.select, 0.95)
        print("float8_reference_glm: the program's decode steps select at "
              "recall 0.95", flush=True)
    return harness.main()


def at_recall(select, recall):
    """``dsa.select`` whose positions form (a decode step) names, of each
    query's k picks, the best ``recall`` x k and then the best keys beyond
    the k-th instead of the rest."""
    import jax.numpy as jnp

    def low(iq, iw, keys, q_pos, topk):
        out = select(iq, iw, keys, q_pos, topk)
        if not isinstance(out, tuple):
            return out
        k = out[0].shape[-1]
        m = max(1, round((1 - recall) * k))
        more = select(iq, iw, keys, q_pos, k + m)
        if more[0].shape[-1] < k + m:       # no keys beyond the k-th
            return out
        return tuple(jnp.concatenate([a[..., :k - m], a[..., k:]], axis=-1)
                     for a in more)

    return low


if __name__ == "__main__":
    sys.exit(main())
