"""What the KDA layers of a program must move in a decode step, from its
shapes: the function behind ``kda_step_roofline`` (``glm-5.3-flash-l5``; the
keys are the catalog's ``linear_attn_config`` and the configuration file's
``assumed_sizes.kda_gate_rank``. ``lib/hybrid_cost.py`` counts Ling's mixer,
with a full decay-gate matrix and a head-wise output gate, from Ling's key
names).

Every KDA layer reads its mixer's weights as stored, once a step; reads and
writes the float32 recurrent matrix ``[H, dk, dk]`` and the convolution tail
``[K - 1, 3 H dk]`` of each slot that is owed a token, and no other slot's;
takes a row a live slot in and hands one out. The arithmetic is nothing
beside that (a row's projections and its 7 H dk^2 state operations are 2.8e8
FLOP a layer against 551 MB of weights): the part waits for memory.
"""

WEIGHT_BYTES = 4        # float32 storage (PERF.md section 7)
STATE_BYTES = 4         # the recurrent matrix
ROW_BYTES = 2           # bf16 rows and convolution tails


def layers(cfg):
    return len(cfg["linear_attn_config"]["kda_layers"])


def mixer_params(cfg):
    """One mixer: q, k, v and output projections, the decay gate and the
    output gate through ``kda_gate_rank``, beta, three depth-wise
    convolutions, A_log, dt_bias and the output norm's gain."""
    lin = cfg["linear_attn_config"]
    d, h, dk = cfg["hidden_size"], lin["num_heads"], lin["head_dim"]
    c, r = h * dk, cfg["assumed_sizes"]["kda_gate_rank"]
    return (4 * d * c + 2 * (d * r + r * c) + d * h
            + 3 * lin["short_conv_kernel_size"] * c + h + c + dk)


def slot_state_bytes(cfg):
    """One slot's state in one layer: the matrix and the tail."""
    lin = cfg["linear_attn_config"]
    h, dk = lin["num_heads"], lin["head_dim"]
    tail = (lin["short_conv_kernel_size"] - 1) * 3 * h * dk * ROW_BYTES
    return h * dk * dk * STATE_BYTES + tail


def step_bytes(cfg, *, live):
    """Bytes the ``kda.*`` part of one decode step must move with ``live``
    slots owed a token: every layer's weights, the live slots' state read
    and written, their rows in and out."""
    rows = 2 * live * cfg["hidden_size"] * ROW_BYTES
    return layers(cfg) * (mixer_params(cfg) * WEIGHT_BYTES
                          + 2 * live * slot_state_bytes(cfg) + rows)
