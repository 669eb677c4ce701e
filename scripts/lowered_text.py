"""The lowered text of every program a ``DecodeServer`` dispatches, for each of
``tests/test_serving.py``'s ``KINDS`` (what a slot holds, how a prompt is
admitted), greedy and sampled — to hold a refactor of the serving path to
"the programs a cell compiles stay letter for letter" before any chip time:

    JAX_PLATFORMS=cpu python scripts/lowered_text.py /tmp/after
    JAX_PLATFORMS=cpu python scripts/lowered_text.py /tmp/before <parent checkout>
    diff -r /tmp/before /tmp/after

One file a program (StableHLO as ``Lowered.as_text()`` prints it, without
locations) and one with the served tokens. The second argument: import the
package from that checkout; the models and the traffic stay this one's. CPU
lowering at test size: the same text means the same program here, not the same
time on a chip.
"""

import argparse
import hashlib
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK = [(5, 9), (11, 1), (7, 2), (20, 10), (3, 6)]     # prompt, new tokens


class _Recorded:
    """What ``jax.jit`` returns while a server is recorded: the jitted
    function, which notes its lowered text at its first call."""

    def __init__(self, jit, texts, tag, fn, kw):
        self.fn, self.run, self.texts, self.tag = fn, jit(fn, **kw), texts, tag

    def __call__(self, *args):
        inner = getattr(self.fn, "func", self.fn)
        rung = next((a.shape[1] for a in args[2:4]
                     if getattr(a, "ndim", 0) == 2 and a.shape[0] == 1), "")
        name = f"{self.tag}.{inner.__name__}{rung and f'.P{rung}'}"
        if name not in self.texts:
            self.texts[name] = self.run.lower(*args).as_text()
        return self.run(*args)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("tree", nargs="?", default=HERE)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    import jax
    import numpy as np

    import deeplearning4j_tpu
    assert os.path.dirname(os.path.dirname(
        deeplearning4j_tpu.__file__)) == os.path.abspath(args.tree)
    from deeplearning4j_tpu.serving import DecodeServer
    from deeplearning4j_tpu.serving import engine as eng

    spec = importlib.util.spec_from_file_location(
        "serving_kinds", os.path.join(HERE, "tests", "test_serving.py"))
    kinds = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kinds)
    texts, jit = {}, jax.jit
    for kind, (make, _, block) in kinds.KINDS.items():
        for sampling in ({}, {"temperature": 0.8, "top_k": 7}):
            tag = f"{kind}.{'sampled' if sampling else 'greedy'}"
            jax.jit = lambda fn, **kw: _Recorded(jit, texts, tag, fn, kw)
            eng.PREFILL_BLOCK, default = block or eng.PREFILL_BLOCK, \
                eng.PREFILL_BLOCK
            try:
                server = DecodeServer(make(), slots=3, max_len=32,
                                      buckets=(8, 16, 32), **sampling)
                rng = np.random.default_rng(0)
                reqs = [server.submit(rng.integers(1, 61, n).astype(np.int32),
                                      m, seed=i)
                        for i, (n, m) in enumerate(WORK)]
                server.drain()
            finally:
                jax.jit, eng.PREFILL_BLOCK = jit, default
            texts[tag + ".tokens"] = repr([r.tokens for r in reqs])
    os.makedirs(args.out, exist_ok=True)
    for name, text in sorted(texts.items()):
        with open(os.path.join(args.out, name + ".txt"), "w") as f:
            f.write(text)
        print(hashlib.sha256(text.encode()).hexdigest()[:16], name)


if __name__ == "__main__":
    main()
