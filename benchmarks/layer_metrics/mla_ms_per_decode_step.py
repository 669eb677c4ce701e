"""Device time a decode step spends in the latent-attention mixer: ops under
the program's ``mla.proj`` and ``mla.attend`` scopes inside the decode
program (the projections, the write of the new latent rows, the absorbed
attention over the cached rows), over the decode steps in the trace."""

from benchmarks.layer_metrics import _hybrid

NAME, UNIT, LAYER, MOVES = ("mla_ms_per_decode_step", "ms",
                            "latent attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _hybrid.decode_ms(trace, counters, ctx, "mla")
