"""Device time a decode step spends in the attention layers outside the
kernel: ops under ``attn.proj`` (``wq``/``wk``/``wv``, RoPE, ``wo`` and its
residual add) and ``kv.write`` (the new rows' scatter into the slot pool, the
decode kernel's work list and operand layout);
inside the decode program, over the decode steps in the trace, all layers
together (``_scopes``: each op once, a Pallas kernel never)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("attn_proj_ms_per_decode_step", "ms", "serving",
                            "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.decode_ms(trace, counters, ctx),
                      "attn.proj", "kv.write")
