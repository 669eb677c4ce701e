"""The layout the TPU compiler gives the flat updater's vectors in ResNet-18's
four-device chunk program, and the program's temporaries — compile-only, for a
described ``v5e:2x2``, through ``benchmarks/tools/compile_rehearsal.resnet``'s
own recipe (nothing runs; none of this is a chip number):

    JAX_PLATFORMS=cpu python scripts/flat_updater_layout.py --batch 1024,16384
    JAX_PLATFORMS=cpu python scripts/flat_updater_layout.py --batch 1024 <parent checkout>

A flat vector is any array of the compiled text that holds the network's
parameter count, or that count rounded up to ``nn.updater``'s unit of 1,024: a
dense one reads ``f32[11177984]{0:T(1024)}``; the form PR 50 cured read
``f32[1117697,10]{1,0:T(8,128)}``, ten of every 128 lanes. The argument:
take the package and the recipe from that checkout.
"""

import argparse
import collections
import importlib.util
import math
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE = re.compile(r"\b([a-z]+\d+)\[([\d,]+)\]\{([^}]*)\}")


def flat_shapes(text, sizes):
    """``{shape with layout: uses}`` of the arrays in ``text`` whose element
    count is one of ``sizes``."""
    found = collections.Counter()
    for dtype, dims, layout in SHAPE.findall(text):
        if math.prod(int(d) for d in dims.split(",")) in sizes:
            found[f"{dtype}[{dims}]{{{layout}}}"] += 1
    return found


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("tree", nargs="?", default=HERE)
    ap.add_argument("--batch", default="1024,16384",
                    help="global batches over the four chips")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    spec = importlib.util.spec_from_file_location(
        "compile_rehearsal",
        os.path.join(tree, "benchmarks", "tools", "compile_rehearsal.py"))
    rehearsal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rehearsal)      # puts ``tree`` first on sys.path

    import jax
    from jax.experimental import topologies

    import deeplearning4j_tpu
    from deeplearning4j_tpu.models import resnet18
    assert os.path.dirname(os.path.dirname(
        deeplearning4j_tpu.__file__)) == tree

    cfg = rehearsal._load("benchmarks/configs/resnet18-cifar10.json")
    n = sum(int(leaf.size) for leaf in jax.tree_util.tree_leaves(
        resnet18(num_classes=cfg["num_classes"]).init().params))
    sizes = {n, n + -n % 1024}
    print(f"tree {tree}: {n:,} parameters, a flat vector holds {sorted(sizes)}",
          flush=True)

    report = rehearsal._report

    def report_layout(name, compile_fn):
        compiled = report(name, compile_fn)
        if compiled is not None:
            m = compiled.memory_analysis()
            print(f"  temp {m.temp_size_in_bytes:,} bytes")
            shapes = flat_shapes(compiled.as_text(), sizes)
            for shape, uses in sorted(shapes.items(),
                                      key=lambda kv: -kv[1]):
                print(f"  {shape} x {uses}")
            padded = sorted(s for s in shapes if "," in s[:s.index("]")])
            print(f"  two-dimensional forms of a flat vector: "
                  f"{padded or 'none'}", flush=True)
        return compiled

    rehearsal._report = report_layout
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    rehearsal.resnet(topo, [int(b) for b in args.batch.split(",")])


if __name__ == "__main__":
    main()
