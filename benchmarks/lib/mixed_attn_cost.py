"""Bytes the attention pools and the routed experts of the ``mellum``
configuration (``mellum2-12b-a2.5b-l4``) must move in a decode step, from the
configuration's shapes alone. Kept with the benchmark so that no PR to the
program can move the numerator of ``win_attn_roofline``, ``full_attn_roofline``
or ``moe_dense_roofline``, nor the denominator of ``kv_pool_bytes_share``.
Every size is the configuration file's; the stored width of a parameter is
``assumed.weight_storage``'s (``param_bytes``).

- **the pool reads**: K and V rows the live slots hold, ``min(c + 1,
  sliding_window)`` a sliding layer and ``c + 1`` a full layer at cursor ``c``
  (the server's own counts, ``kv_rows_window`` and ``kv_rows_full``), each
  ``Hkv dh`` numbers in the pool's dtype.
- **the routed experts**: the router ``[D, E]`` of every layer, the three
  matrices ``[D, F]``, ``[D, F]``, ``[F, D]`` of every (layer, expert) cell
  some live row chose, with ``F`` = ``moe_intermediate_size`` (this model's
  ``intermediate_size`` is the width of a dense layer it does not have, which
  is why ``lib/moe_cost.expert_bytes`` does not serve here), the rows in and
  out. What it need not move: experts no row chose, per-expert intermediates.
- **what one length of pool would hold**: ``max_len`` rows a slot for every
  layer, sliding or full: what ``state_bytes`` of the two pools is a share of.
"""

from __future__ import annotations

PARAM_BYTES = {"float32": 4, "bfloat16": 2}


def layer_counts(cfg: dict) -> dict:
    """``{"window": n, "full": n}`` of the layers kept."""
    kinds = cfg["layer_types"]
    full = sum(1 for k in kinds if k == "full_attention")
    return {"window": len(kinds) - full, "full": full}


def param_bytes(cfg: dict) -> int:
    """Bytes a stored parameter takes (``assumed.weight_storage`` starts
    with the dtype's name)."""
    return PARAM_BYTES[cfg["assumed"]["weight_storage"].split(",")[0]]


def kv_row_bytes(cfg: dict, act_bytes: int = 2) -> int:
    """One position's K and V in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * act_bytes


def expert_bytes(cfg: dict) -> int:
    """Stored bytes of one expert's three matrices."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * param_bytes(cfg))


def routed_step_bytes(cfg: dict, *, tokens: float, touched: float,
                      act_bytes: int = 2) -> float:
    """Bytes the routed experts of ALL layers must move in one step over
    ``tokens`` rows a layer, when ``touched`` (layer, expert) cells, summed
    over the layers, received at least one row."""
    layers = cfg["num_hidden_layers"]
    router = cfg["hidden_size"] * cfg["num_experts"] * param_bytes(cfg)
    rows = 2 * tokens * cfg["hidden_size"] * act_bytes        # in and out
    return touched * expert_bytes(cfg) + layers * (router + rows)


def one_length_pool_bytes(cfg: dict, *, slots: int, max_len: int,
                          act_bytes: int = 2) -> int:
    """What the K/V state would take with ``max_len`` rows a slot for every
    layer, whatever its kind."""
    return (cfg["num_hidden_layers"] * slots * max_len
            * kv_row_bytes(cfg, act_bytes))
