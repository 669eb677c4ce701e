"""Device time a decode step spends in the two ends of the stack: ops under
``lm.head`` (final norm, unembedding, the sampler) and ``lm.embed`` (the token
gather and the cast into the compute dtype);
inside the decode program, over the decode steps in the trace, all layers
together (``_scopes``: each op once, a Pallas kernel never)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("head_ms_per_decode_step", "ms", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.decode_ms(trace, counters, ctx),
                      "lm.head", "lm.embed")
