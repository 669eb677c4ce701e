"""What the K/V state of a model with sliding-window and full layers takes,
as a share of what one length of pool would: the server's own
``state_bytes`` of its two pools (``kv``, the ``T_max`` rows of the full
layers, and ``ring``, the window layers' rings) over ``max_len`` rows a slot
for every layer (``lib/mixed_attn_cost.one_length_pool_bytes``). 0.27 at the
cell's cut with a ring of 1,024 rows; 1.0 if the ring is lost. ``None`` for a
program that reports no such bytes."""

from benchmarks.lib import mixed_attn_cost

NAME, UNIT, LAYER, MOVES = ("kv_pool_bytes_share", "ratio",
                            "window and full attention", "serve_tpot_p50_ms")


def compute(trace, spans, counters, ctx):
    held = counters.get("state_bytes_kv")
    if not held:
        return None
    sv = ctx["cell"]["server"]
    return (held + counters.get("state_bytes_ring", 0)) / (
        mixed_attn_cost.one_length_pool_bytes(
            ctx["config"], slots=int(sv["slots"]),
            max_len=int(sv["max_len"])))
