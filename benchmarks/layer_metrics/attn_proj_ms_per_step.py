"""Device time a training step spends in the attention layers outside the flash
kernels, forward and backward: ops under ``attn.proj`` (``wq``/``wk``/``wv``,
RoPE, the kv-head repeat, ``wo`` and its residual add);
inside the step program, over the steps in the trace (``_scopes``: each op
once, a Pallas kernel never)."""

from benchmarks.layer_metrics import _scopes

NAME, UNIT, LAYER, MOVES = ("attn_proj_ms_per_step", "ms", "LM training",
                            "train_mfu")


def compute(trace, spans, counters, ctx):
    return _scopes.of(_scopes.step_ms(trace, counters, ctx),
                      "attn.proj")
