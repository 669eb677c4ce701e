"""Run a cell with the plain reference's weights rounded to float8 e4m3, the
precision below bf16: the cell must then come out NOT correct. One of the two
readings every limit of a reference check is set from (PERF.md section 6).

    python3 benchmarks/tools/float8_reference.py --workload olmoe-serve-chat --seed 7 --seconds 20 --trace 0

The arguments are ``benchmarks/run.py``'s. Covers the cells whose check calls
``reference_lm.mean_nll`` / ``tail_logits`` or
``reference_olmoe.forward_tail``. The matrices are rounded in place (donated:
7 GiB of OLMoE weights have no room for a copy), which is safe because only
the reference reads them once the window is over.

The rounding is arithmetic, not a cast to float8 and back: XLA:TPU elides
that pair (PR 25's chip call 19 read the unrounded numbers and passed), and
``lax.reduce_precision(x, 4, 3)`` flushes e4m3's subnormals, half of weights
drawn at 0.02.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp


def e4m3(x):
    """Round to the nearest float8 e4m3fn value: 3 mantissa bits, least
    normal exponent -6, subnormals kept (bit-equal to the cast on the CPU
    for |x| <= 448)."""
    _, e = jnp.frexp(x)
    q = jnp.exp2((jnp.maximum(e - 1, -6) - 3).astype(x.dtype))
    return jnp.clip(jnp.round(x / q) * q, -448.0, 448.0)


def rounded(fn):
    """``fn(params, ...)`` reading ``params`` with its matrices rounded."""
    round_leaf = jax.jit(e4m3, donate_argnums=0)
    cache = {}

    def call(params, *args, **kw):
        if id(params) not in cache:
            cache.clear()
            cache[id(params)] = jax.tree_util.tree_map(
                lambda x: round_leaf(x) if x.ndim >= 2 else x, params)
        return fn(cache[id(params)], *args, **kw)

    return call


def main() -> int:
    from benchmarks import run as harness
    from benchmarks.lib import reference_lm, reference_olmoe

    reference_olmoe.forward_tail = rounded(reference_olmoe.forward_tail)
    reference_lm.mean_nll = rounded(reference_lm.mean_nll)
    reference_lm.tail_logits = rounded(reference_lm.tail_logits)
    print("float8_reference: the reference reads weights rounded to "
          "float8_e4m3fn", flush=True)
    return harness.main()


if __name__ == "__main__":
    sys.exit(main())
