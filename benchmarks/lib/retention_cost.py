"""Bytes and operations the power-retention layers of the ``brumby``
configuration (``brumby-14b-l4``) must move, from the configuration's shapes
alone. Kept with the benchmark so that no PR to the program can move the
numerator of ``ret_roofline`` or ``ret_scan_roofline``. Every size is the
configuration file's: the rows of a head's state are ``state_rows`` (the
``D`` stored, ``assumed.state_rows`` says which layout), the normaliser is
the whole ``[d, d]`` matrix a head, both float32.

- **a decode step's recurrence** (``ret_roofline``: the step kernel alone):
  each live slot's ``S`` ``[Hkv, D, d]`` and ``Z`` ``[Hkv, d, d]``, read once
  and written once, and the kernel's small operands: q and o at the query
  heads' count, k, v and the gate's row at the key/value heads', float32.
  What it need not move: a slot that owes nothing; any ``phi``.
- **a prompt's recurrence** (``ret_scan_roofline``): per token and layer the
  products with the state, ``[1, D] x [D, d]`` a query head (the read) and a
  key/value head (the update), by the ``D`` stored; the normaliser's
  ``q^T Z q`` and ``k k^T``; and inside a chunk of ``chunk`` positions the
  masked ``q . k`` and ``w v`` products, counted causally ((chunk + 1) / 2
  keys a query on average). Compute bound: the share is of the MXU's peak.
"""

from __future__ import annotations


def layers(cfg: dict) -> int:
    return len(cfg["kept_layers"])


def slot_state_bytes(cfg: dict, rows: int = None) -> int:
    """One slot's ``(S, Z)`` in one layer. ``rows``: another ``D`` than the
    one stored (the symmetric power itself is 8,256)."""
    hkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    rows = cfg["state_rows"] if rows is None else rows
    return hkv * (rows * d + d * d) * 4


def step_operand_bytes(cfg: dict) -> int:
    """The kernel's small operands for one live slot in one layer: q in and
    o out [H, d], k, v and the gate's row [Hkv, d], float32."""
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return (2 * h + 3 * hkv) * d * 4


def step_bytes(cfg: dict, *, live: float) -> float:
    """Bytes the step kernels of ALL layers must move in one decode step
    with ``live`` slots owed a token."""
    return layers(cfg) * live * (2 * slot_state_bytes(cfg)
                                 + step_operand_bytes(cfg))


def scan_token_flops(cfg: dict, chunk: int) -> float:
    """FLOP of the chunked recurrence for one token in one layer."""
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    rows = cfg["state_rows"]
    state = 2.0 * (h + hkv) * rows * d              # the read, the update
    norm = h * (2.0 * d * d + 2.0 * d) + hkv * 2.0 * d * d
    inside = h * 2.0 * (2.0 * d) * (chunk + 1) / 2  # q . k and w v, causal
    return state + norm + inside


def scan_seconds(cfg: dict, *, tokens: float, chunk: int,
                 flops_per_s: float) -> float:
    """The least time the chip needs for the recurrence of ``tokens`` prompt
    tokens in every layer."""
    return layers(cfg) * tokens * scan_token_flops(cfg, chunk) / flops_per_s
