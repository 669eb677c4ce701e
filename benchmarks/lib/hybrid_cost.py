"""Bytes and operations a decode step of the hybrid model
(``ling-3.0-flash-vl-l7``) must move in its three parts, from the
configuration's shapes alone. Kept with the benchmark so that no PR to the
program can move the numerator of ``kda_roofline`` or
``routed_share_roofline``.

- **KDA part** (every KDA layer): the mixer's weights, read once a step; each
  live slot's recurrent matrix ``[H, dk, dk]`` in float32, read and written;
  its convolution tail ``[K - 1, 3 H dk]``, read and written; the rows in and
  out. What it need not move: a slot that owes nothing.
- **MLA part** (every MLA layer): the mixer's weights; each live slot's
  cached latent rows up to its cursor, read; one new row a slot, written.
- **routed share**: the router (all ``E`` outputs), the expert bias and the
  shared expert of every expert layer; the three matrices of every held
  expert some live row chose (a weight is read once a step however many rows
  use it); the rows in and out. What it need not move: held experts no row
  chose, and the experts held elsewhere.

Which published layers are kept, and which of them are MLA, is read from the
configuration as the driver reads it (``kept_layers``, ``layer_group_size``,
``first_k_dense_replace``).
"""

from __future__ import annotations


def layer_counts(cfg: dict) -> dict:
    """``{"kda": n, "mla": n, "moe": n, "glu": n}`` of the layers kept."""
    period = cfg["layer_group_size"]
    mla = sum(1 for i in cfg["kept_layers"] if (i + 1) % period == 0)
    n = len(cfg["kept_layers"])
    dense = min(n, cfg["first_k_dense_replace"])
    return {"kda": n - mla, "mla": mla, "moe": n - dense, "glu": dense}


def _kda_width(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def kda_layer_params(cfg: dict) -> int:
    """Parameters of one KDA mixer: q, k, v, decay-gate and output
    projections, beta and the head-wise gate, three depthwise convolutions,
    A_log, dt_bias and the output norm's gain."""
    d, h, c = cfg["hidden_size"], cfg["num_attention_heads"], _kda_width(cfg)
    return (5 * d * c + 2 * d * h + 3 * cfg["short_conv_kernel_size"] * c
            + h + c + cfg["head_dim"])


def kda_slot_state_bytes(cfg: dict, act_bytes: int = 2) -> int:
    """One slot's state in one KDA layer: the float32 matrix and the tail."""
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    tail = (cfg["short_conv_kernel_size"] - 1) * 3 * h * dk * act_bytes
    return h * dk * dk * 4 + tail


def kda_step_bytes(cfg: dict, *, live: float, param_bytes: int = 4,
                   act_bytes: int = 2) -> float:
    """Bytes the KDA part of ALL its layers must move in one decode step
    with ``live`` slots owed a token."""
    rows = 2 * live * cfg["hidden_size"] * act_bytes
    per_layer = (kda_layer_params(cfg) * param_bytes
                 + 2 * live * kda_slot_state_bytes(cfg, act_bytes) + rows)
    return layer_counts(cfg)["kda"] * per_layer


def kda_step_flops(cfg: dict, *, live: float) -> float:
    """Multiply-adds x 2: the seven projections, and on the state the decay,
    S'^T k, the rank-one update and S^T q (about 7 H dk^2 a row)."""
    d, h, c = cfg["hidden_size"], cfg["num_attention_heads"], _kda_width(cfg)
    per_row = 2 * (5 * d * c + 2 * d * h) + 7 * h * cfg["head_dim"] ** 2
    return layer_counts(cfg)["kda"] * live * per_row


def mla_layer_params(cfg: dict) -> int:
    d, h, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * h * (dn + dr) + d * (r + dr) + r + r * h * (dn + dv)
            + h * dv * d + d * h)


def mla_step_bytes(cfg: dict, *, live: float, context: float,
                   param_bytes: int = 4, act_bytes: int = 2) -> float:
    """Bytes the MLA part must move in one decode step: ``live`` slots, each
    attending ``context`` cached latent rows (the mean)."""
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    rows = 2 * live * cfg["hidden_size"] * act_bytes
    per_layer = (mla_layer_params(cfg) * param_bytes
                 + live * (context + 1) * width * act_bytes + rows)
    return layer_counts(cfg)["mla"] * per_layer


def mla_step_flops(cfg: dict, *, live: float, context: float) -> float:
    d, h, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    proj = 2 * (d * h * (dn + dr) + d * (r + dr) + h * dv * d + d * h)
    absorbed = 2 * h * (dn * r + r * dv)          # Wuk into q, Wuv out of o
    attend = 2 * h * context * (2 * r + dr)       # scores and the sum
    return layer_counts(cfg)["mla"] * live * (proj + absorbed + attend)


def expert_bytes(cfg: dict, param_bytes: int) -> int:
    """Stored bytes of one routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * param_bytes


def routed_step_bytes(cfg: dict, *, live: float, touched: float,
                      param_bytes: int = 4, act_bytes: int = 2) -> float:
    """Bytes the routed share of ALL expert layers must move in one step
    over ``live`` rows a layer, when ``touched`` (layer, held expert) cells,
    summed over the layers, received at least one row."""
    d, e = cfg["hidden_size"], cfg["published"]["num_experts"]
    shared = 3 * d * cfg["moe_shared_expert_intermediate_size"]
    fixed = (d * e + e + shared) * param_bytes
    rows = 2 * live * d * act_bytes
    return (touched * expert_bytes(cfg, param_bytes)
            + layer_counts(cfg)["moe"] * (fixed + rows))


def routed_step_flops(cfg: dict, *, live: float, pairs_here: float) -> float:
    """Multiply-adds x 2 for ``live`` rows a layer of which each sends
    ``pairs_here`` (token, expert) pairs to an expert held here: the router
    over all E, those pairs' three projections, the shared expert."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    per_row = (2 * d * cfg["published"]["num_experts"]
               + pairs_here * 3 * 2 * d * f
               + 3 * 2 * d * cfg["moe_shared_expert_intermediate_size"])
    return layer_counts(cfg)["moe"] * live * per_row
