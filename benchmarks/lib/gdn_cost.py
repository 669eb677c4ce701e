"""Bytes and operations the Gated DeltaNet layers and the pool read of the
``qwen3_next`` configuration (``qwen3-next-80b-a3b-l4``) must move, from the
configuration's shapes alone. Kept with the benchmark so that no PR to the
program can move the numerator of ``gdn_roofline``, ``gdn_scan_roofline`` or
``pool_attn_roofline``. Every size is the configuration file's; the stored
width of a parameter is ``assumed.weight_storage``'s (``param_bytes``).

- **a decode step's Gated DeltaNet part** (every such layer): the mixer's
  weights, read once a step; each live slot's recurrent matrix ``[Hv, dk,
  dv]`` in float32, read and written; its convolution tail ``[K - 1, 2 Hk dk
  + Hv dv]``, read and written; the rows in and out. What it need not move:
  a slot that owes nothing.
- **a prompt's recurrence** (``gdn_scan_roofline``): the recurrence's own
  work, whatever algorithm computes it: per token, layer and value head the
  product ``S'^T k``, the rank-one update and ``S^T q``, ``6 dk dv`` FLOP,
  and q, k, v, o in float32 and the two gates moved once. Neither the
  state's traffic (a kernel keeps it on the chip) nor a chunked form's
  extra products are counted: a later kernel is read by the same yardstick.
- **the pool read**: K and V rows of the live slots up to their cursors, over
  the attention layers (``kv_rows``, the server's own count from its
  cursors), each ``Hkv dh`` numbers in the pool's dtype.

Which published layers are kept, and which of them are attention, is read
from the configuration as the driver reads it (``kept_layers``,
``full_attention_interval``).
"""

from __future__ import annotations

PARAM_BYTES = {"float32": 4, "bfloat16": 2}


def layer_counts(cfg: dict) -> dict:
    """``{"gdn": n, "attn": n}`` of the layers kept."""
    period = cfg["full_attention_interval"]
    attn = sum(1 for i in cfg["kept_layers"] if (i + 1) % period == 0)
    return {"gdn": len(cfg["kept_layers"]) - attn, "attn": attn}


def param_bytes(cfg: dict) -> int:
    """Bytes a stored parameter takes (``assumed.weight_storage`` starts
    with the dtype's name)."""
    return PARAM_BYTES[cfg["assumed"]["weight_storage"].split(",")[0]]


def _widths(cfg: dict):
    """``(Hk dk, Hv dv)``."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def gdn_layer_params(cfg: dict) -> int:
    """Parameters of one mixer: ``W_qkvz``, ``W_ba``, the taps, ``A_log``,
    ``dt_bias``, the output norm's gain, ``W_o``."""
    d, hv = cfg["hidden_size"], cfg["linear_num_value_heads"]
    ck, cv = _widths(cfg)
    return (d * (2 * ck + 2 * cv) + d * 2 * hv
            + cfg["linear_conv_kernel_dim"] * (2 * ck + cv) + 2 * hv
            + cfg["linear_value_head_dim"] + cv * d)


def gdn_slot_state_bytes(cfg: dict, act_bytes: int = 2) -> int:
    """One slot's state in one layer: the float32 matrix and the tail."""
    ck, cv = _widths(cfg)
    matrix = (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
              * cfg["linear_value_head_dim"] * 4)
    return matrix + (cfg["linear_conv_kernel_dim"] - 1) * (
        2 * ck + cv) * act_bytes


def gdn_step_bytes(cfg: dict, *, live: float, act_bytes: int = 2) -> float:
    """Bytes the Gated DeltaNet part of ALL its layers must move in one
    decode step with ``live`` slots owed a token."""
    rows = 2 * live * cfg["hidden_size"] * act_bytes
    per_layer = (gdn_layer_params(cfg) * param_bytes(cfg)
                 + 2 * live * gdn_slot_state_bytes(cfg, act_bytes) + rows)
    return layer_counts(cfg)["gdn"] * per_layer


def gdn_step_flops(cfg: dict, *, live: float) -> float:
    """Multiply-adds x 2: the projections, and on the state the decay,
    ``S'^T k``, the rank-one update and ``S^T q`` (about 7 dk dv a head)."""
    d, hv = cfg["hidden_size"], cfg["linear_num_value_heads"]
    ck, cv = _widths(cfg)
    per_row = (2 * (d * (2 * ck + 2 * cv) + d * 2 * hv + cv * d)
               + 7 * hv * cfg["linear_key_head_dim"]
               * cfg["linear_value_head_dim"])
    return layer_counts(cfg)["gdn"] * live * per_row


def scan_token_flops(cfg: dict) -> float:
    """FLOP of the recurrence for one token in one layer: ``6 dk dv`` a
    value head."""
    return (6.0 * cfg["linear_num_value_heads"]
            * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"])


def scan_token_bytes(cfg: dict) -> float:
    """Bytes of the recurrence for one token in one layer: q and k at the
    value heads' count, v in and o out, float32, and the two gates."""
    hv = cfg["linear_num_value_heads"]
    return 4.0 * (2 * hv * cfg["linear_key_head_dim"]
                  + 2 * hv * cfg["linear_value_head_dim"] + 2 * hv)


def scan_seconds(cfg: dict, *, tokens: float, flops_per_s: float,
                 bytes_per_s: float) -> float:
    """The least time the chip needs for the recurrence of ``tokens`` prompt
    tokens in every Gated DeltaNet layer: the larger of its FLOP over the
    peak and its bytes over the bandwidth."""
    n = layer_counts(cfg)["gdn"] * tokens
    return max(n * scan_token_flops(cfg) / flops_per_s,
               n * scan_token_bytes(cfg) / bytes_per_s)


def kv_row_bytes(cfg: dict, act_bytes: int = 2) -> int:
    """One position's K and V in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * act_bytes
