"""Device time a prefill block spends in the hyper-connections: ops under
the ``hc.map`` and ``hc.mix`` scopes inside the prefill programs, over the
blocks the trace holds (the program's ``serve.prefill_block`` spans and, for
a prompt's last block, ``serve.prefill``). A block holds back every live
slot's next token, so this moves TPOT as well as TTFT."""

from benchmarks.layer_metrics import _hybrid, _program_spans

NAME, UNIT, LAYER, MOVES = ("hc_ms_per_prefill_block", "ms", "residual path",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    found = _hybrid.scoped(trace, counters, ctx, "hc")
    blocks = (len(_program_spans.spans(trace, "serve.prefill") or ())
              + len(_program_spans.spans(trace, "serve.prefill_block") or ()))
    if not found or not blocks:
        return None
    return 1e3 * found["prefill"][0] / blocks
