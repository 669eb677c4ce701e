"""Bytes and operations the routed experts of an OLMoE-style block must move,
from the configuration's shapes alone. Kept with the benchmark so that no PR
to the program can move the numerator of ``moe_roofline``.

One routed layer holds a router ``[D, E]`` and, per expert, three matrices
``[D, F]``, ``[D, F]``, ``[F, D]``. A step over ``tokens`` rows must read the
router, every matrix of every expert some row chose (a weight is read once a
step however many rows use it), the rows themselves, and write as many rows
back. What it need not move: experts no row chose, per-expert intermediates
(they fit on chip at decode sizes), and a second copy of the weights in
another dtype.
"""

from __future__ import annotations


def expert_bytes(cfg: dict, param_bytes: int) -> int:
    """Stored bytes of one expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] * param_bytes


def routed_step_bytes(cfg: dict, *, tokens: float, touched: float,
                      param_bytes: int = 4, act_bytes: int = 2) -> float:
    """Bytes the routed experts of ALL layers must move in one step over
    ``tokens`` rows a layer, when ``touched`` (layer, expert) cells, summed
    over the layers, received at least one row. ``param_bytes`` is the
    weights' stored width (float32 here), ``act_bytes`` the activations'."""
    layers = cfg["num_hidden_layers"]
    router = cfg["hidden_size"] * cfg["num_experts"] * param_bytes
    rows = 2 * tokens * cfg["hidden_size"] * act_bytes        # in and out
    return touched * expert_bytes(cfg, param_bytes) + layers * (router + rows)


def routed_step_flops(cfg: dict, *, tokens: float) -> float:
    """Multiply-adds x 2 the algorithm needs for ``tokens`` rows a layer in
    all layers: the router, and three projections for each of a row's
    ``num_experts_per_tok`` experts."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_row = 2 * d * cfg["num_experts"] \
        + cfg["num_experts_per_tok"] * 3 * 2 * d * f
    return cfg["num_hidden_layers"] * tokens * per_row
