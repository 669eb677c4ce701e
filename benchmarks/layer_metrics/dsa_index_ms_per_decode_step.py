"""Device time a decode step spends in the lightning indexer: ops under the
program's ``dsa.index`` scope inside the decode program (the indexer's
projections, the write of the new index keys, the scores against the slots'
cached keys and the exact top-k), self time, over the decode steps in the
trace. ``None`` where there is nothing to read: no trace, a program without
the scope (the parent of PR 31), a cell without ``dsa_index_scopes``."""

from benchmarks.layer_metrics import _hybrid

NAME, UNIT, LAYER, MOVES = ("dsa_index_ms_per_decode_step", "ms",
                            "sparse attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _hybrid.decode_ms(trace, counters, ctx, "dsa_index")
