"""Device time a decode step spends in the dense feed-forwards: ops under the
program's ``ffn.dense`` scope (the ``glu`` and ``mlp`` branches of
``TransformerLM._ffn``: weight casts, matmuls, activation, residual add);
inside the decode program, over the decode steps in the trace, all layers
together (``_scopes``: each op once, a Pallas kernel never)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("ffn_dense_ms_per_decode_step", "ms", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.decode_ms(trace, counters, ctx),
                      "ffn.dense")
