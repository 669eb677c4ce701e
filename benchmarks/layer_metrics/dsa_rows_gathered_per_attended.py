"""Latent rows the decode steps' gathers fetched over the rows their queries
attended, over the window: the sum of the ``rows_gathered`` attr over the sum
of ``keys_attended`` of the program's ``serve.decode`` spans
(``DecodeServer._book_kv_blocks``: ``index_topk`` rows for each trip of the
decode program's work list of live slots and 'mla' layer, against min(cursor
+ 1, ``index_topk``) a live slot and layer). 1 where the gather follows the
slots that owe a token and every cursor is past ``index_topk``; a program that
gathers for every slot would read slots / live. Host counts of the work list,
as ``dsa_keys_attended_share``'s: the device's side is ``mla_ms_per_decode_
step`` and ``sparse_attend_roofline``. ``None`` where no span carries the
attrs (an untraced run, a program from before PR 34)."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = ("dsa_rows_gathered_per_attended", "ratio",
                            "sparse attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    evs = [e.stats for e in _program_spans.spans(trace, "serve.decode")
           if "rows_gathered" in e.stats and "keys_attended" in e.stats]
    attended = sum(float(s["keys_attended"]) for s in evs)
    return (sum(float(s["rows_gathered"]) for s in evs) / attended
            if attended else None)
