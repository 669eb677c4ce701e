"""Device time a speculative round spends in the model's multi-token-prediction
module: ops under the program's ``mtp`` scopes inside the round program (the
module's token gather ``mtp.embed``, its norms and ``M`` ``mtp.proj``, its own
block and its pass through the shared head under ``mtp/...``), over the rounds
in the trace. A round is the server's decode step: the program is told apart
as the accepted decode readers tell it (``_serve._decode_id``). ``None`` where
the program has no such scope (a parent from before the module)."""

from benchmarks.layer_metrics import _hybrid

NAME, UNIT, LAYER, MOVES = ("mtp_ms_per_round", "ms",
                            "multi-token prediction", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _hybrid.decode_ms(trace, counters, ctx, "mtp")
