"""Sorted rows the passes of the routed experts' sorted form took through the
grouped matmuls, over the (token, expert) pairs held here that they took
them for, over the window's prefills: the sum of the ``moe_rows_run`` attr
over the sum of ``moe_pairs_run`` of the program's ``serve.passes`` spans
(``DecodeServer._read_block`` opens one inside the ``serve.prefill`` span
that read a routing whose layers ran passes: the program's own count,
``routed_ffn``'s ``info["run"]``, and its load in those layers). A pass is a
static number of rows (what an even router sends to this chip's share and a
quarter more, ``routed_experts._pass_rows``) and a block takes as many as
its pairs need, so 1.0 is no waste, about 1.25 an even router over whole
blocks, more where a block's tail is pad rows or holds few pairs; the
parent's sorted form, which took every pair through the matmuls, held here
or not, would read router's experts / held. A prefill on a rung short enough
for the dense form runs no pass and opens no such span. ``None`` where the
trace holds none (an untraced run, a program from before PR 42)."""

from benchmarks.layer_metrics import _program_spans

NAME, UNIT, LAYER, MOVES = ("moe_rows_run_per_pair_here", "ratio",
                            "routed experts", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    evs = [e.stats for e in _program_spans.spans(trace, "serve.passes")
           if "moe_rows_run" in e.stats and "moe_pairs_run" in e.stats]
    pairs = sum(float(s["moe_pairs_run"]) for s in evs)
    return sum(float(s["moe_rows_run"]) for s in evs) / pairs if pairs \
        else None
