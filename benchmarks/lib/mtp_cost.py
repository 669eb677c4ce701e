"""What the latent attention of a speculative round must do, from its shapes:
the function behind ``mla_verify_roofline`` (``gigachat3.1-702b-a36b-l5``; the
keys are the catalog's).

A round verifies two candidates a live slot against the slot's cached latent
rows, in every layer and in the module's block (which attends its own rows at
the same two positions): Q = 2 queries a slot. In the absorbed form a query
head meets a row in ``r + dr`` multiply-adds for its score and ``r`` for its
value, so the two queries of a slot at cursor c cost
``2 x H x (c + 1.5) x (2 r + dr) x 2`` FLOP a layer against ``(c + 2)`` rows of
``latent_row_lanes x 2`` bytes read once for both: twice a decode step's FLOP
a byte, which brings the part near the balance of a v5e (16 slots at 4,000
positions: 0.56 ms of FLOP against 0.92 ms of bytes), so the floor is the
larger of the two. ``wukv`` is read as stored in every layer whatever the
slots hold (42 MB in float32, a third of those bytes), and absorbing it costs
``2 x H x r x (dn + dv) x 2`` FLOP a live slot.
"""

WEIGHT_BYTES = 4        # float32 storage (PERF.md section 7)
CACHE_BYTES = 2         # bf16 latent rows
QUERIES = 2             # candidates a slot and round: the token and the draft


def attention_layers(cfg):
    """Blocks with latent attention in a round: the kept layers and the
    module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def latent_row_lanes(cfg):
    """Lanes a cached latent row takes: ``kv_lora_rank + qk_rope_head_dim``
    numbers and zeros up to a multiple of 128."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def verify_attend_flops(cfg, *, context, live):
    """FLOP of the ``mla.attend`` part of one round: ``live`` slots at a mean
    cursor of ``context``, two queries each, all attention layers."""
    h, r, dr = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                cfg["qk_rope_head_dim"])
    absorb = QUERIES * h * r * (cfg["qk_nope_head_dim"]
                                + cfg["v_head_dim"]) * 2
    attend = QUERIES * h * (context + 1.5) * (2 * r + dr) * 2
    return attention_layers(cfg) * live * (absorb + attend)


def verify_attend_bytes(cfg, *, context, live):
    """Bytes the same part must move: each live slot's rows up to the second
    candidate's position, once a layer; ``wukv`` as stored, once a layer; and
    the rows in and out (query heads in, attention output out)."""
    h = cfg["num_attention_heads"]
    rows = live * (context + 2) * latent_row_lanes(cfg) * CACHE_BYTES
    wukv = WEIGHT_BYTES * cfg["kv_lora_rank"] * h * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    io = live * QUERIES * CACHE_BYTES * h * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return attention_layers(cfg) * (rows + wukv + io)


def verify_attend_floor_s(cfg, peaks, *, context, live):
    """The least time the chip needs for that part: the larger of its FLOP
    over the peak rate and its bytes over the peak bandwidth."""
    return max(
        verify_attend_flops(cfg, context=context, live=live)
        / peaks["bf16_flops"],
        verify_attend_bytes(cfg, context=context, live=live)
        / peaks["hbm_bytes_per_s"])
