"""Device time a decode step spends in ops under no scope of the program's
vocabulary (``deeplearning4j_tpu/scopes.py``) that are no Pallas kernel: what
the scope metrics and the kernel metrics of a cell leave unexplained. With
them it adds up to the time the decode program's ops ran (``_scopes``)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("unscoped_ms_per_decode_step", "ms", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.decode_ms(trace, counters, ctx), "unscoped")
