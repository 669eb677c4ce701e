"""What the decode steps of the window read of the slot pool, as the share of
what every slot's keys would cost: the sum of the ``kv_blocks`` attr over the
sum of ``kv_blocks_pool`` of the program's ``serve.decode`` spans
(``DecodeServer._book_kv_blocks``: the decode kernel's key blocks a layer, of
the live slots and of all). It is the occupancy a decode-path number was read
at. ``None`` where no span carries the attrs (no kernel read, an untraced
run, a program from before PR 28)."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = ("kv_blocks_share", "ratio", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    evs = [e.stats for e in _program_spans.spans(trace, "serve.decode")
           if "kv_blocks" in e.stats and "kv_blocks_pool" in e.stats]
    pool = sum(float(s["kv_blocks_pool"]) for s in evs)
    return sum(float(s["kv_blocks"]) for s in evs) / pool if pool else None
