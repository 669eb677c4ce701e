"""Operations and bytes the algorithm needs, from shapes alone.

Model FLOPs count a multiply-add as two operations, forward plus the
backward pass the algorithm needs (2x forward for every matmul), and never
recomputation: remat or a kernel that recomputes scores does not raise them.
Kernel costs (``flash_kernel_cost``) count what that one kernel call must
compute, recomputed scores included, because that is the kernel's own
roofline.
"""

from __future__ import annotations


# --------------------------------------------------------------------------
# decoder-only LM (keys as in a Hugging Face config.json)
# --------------------------------------------------------------------------
def lm_head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def lm_layer_matmul_params(cfg: dict) -> int:
    """Weights of one block that sit in a matmul: wq, wk, wv, wo and the
    non-gated MLP's two matrices. Biases and norms do no matmul work."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * lm_head_dim(cfg)
    return d * d + 2 * d * kv + d * d + 2 * d * f


def lm_matmul_params(cfg: dict) -> int:
    """All matmul weights a token passes through, tied unembedding
    included (the embedding lookup is a gather, not a matmul)."""
    return (cfg["num_hidden_layers"] * lm_layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def lm_total_params(cfg: dict) -> int:
    """Every stored parameter: matmul weights, MLP biases, norm gains and
    biases, one embedding (tied)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = lm_layer_matmul_params(cfg) + f + d + 4 * d
    return (cfg["num_hidden_layers"] * per_layer
            + d * cfg["vocab_size"] + 2 * d)


def avg_keys_per_query(t: int, window=None) -> float:
    """Mean number of keys a causal query attends over a length-``t``
    sequence: (t+1)/2 without a window; with one, queries q < w see q+1
    keys and the rest see w — exact, never rounded up."""
    if window is None or window >= t:
        return (t + 1) / 2
    w = window
    return (w * (w + 1) / 2 + (t - w) * w) / t


def lm_train_flops_per_token(cfg: dict, t: int) -> float:
    """6 x matmul weights + attention scores and values: 2 matmuls of
    2*head_dim FLOPs per (query, key) pair and head, forward, twice that
    backward -> 12 * layers * hidden * avg_keys."""
    window = cfg.get("sliding_window")
    attn = (12 * cfg["num_hidden_layers"] * cfg["hidden_size"]
            * avg_keys_per_query(t, window))
    return 6.0 * lm_matmul_params(cfg) + attn


def flash_kernel_cost(kernel: str, *, batch: int, heads: int, t: int,
                      head_dim: int, window=None, itemsize: int = 2):
    """``(flops, bytes)`` one call of a flash kernel must do.

    Per (query, key) pair inside the causal band and per head, matmuls of
    2*head_dim FLOPs each: forward 2 (QK^T, PV); dk/dv 4 (recomputed QK^T,
    P^T dO, dO V^T, dS^T Q); dq 3 (recomputed QK^T, dO V^T, dS K). Bytes are
    each operand and result crossing HBM once: [batch, t, heads, head_dim]
    arrays of ``itemsize`` plus the float32 per-row statistics.
    """
    matmuls = {"fwd": 2, "dkdv": 4, "dq": 3}[kernel]
    arrays = {"fwd": 4, "dkdv": 6, "dq": 5}[kernel]       # q k v o | q k v do dk dv | q k v do dq
    rows = {"fwd": 1, "dkdv": 2, "dq": 2}[kernel]         # lse | lse, delta
    pairs = batch * heads * t * avg_keys_per_query(t, window)
    flops = matmuls * 2.0 * head_dim * pairs
    nbytes = (arrays * batch * t * heads * head_dim * itemsize
              + rows * batch * heads * t * 4)
    return flops, float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take and which bound sets it."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


# --------------------------------------------------------------------------
# CIFAR-form ResNet (3x3 stem, basic blocks, 1x1 projection on a change of
# stride or width)
# --------------------------------------------------------------------------
def resnet_conv_layers(cfg: dict):
    """``[(name, multiply_adds_per_sample)]`` for every conv and the
    classifier, in forward order."""
    size = cfg["image_size"]
    layers = []

    def conv(name, c_in, c_out, k, out_size):
        layers.append((name, out_size * out_size * c_out * c_in * k * k))

    c_prev = cfg["stem_channels"]
    conv("stem", cfg["image_channels"], c_prev, 3, size)
    for s, c in enumerate(cfg["stage_channels"]):
        for b in range(cfg["blocks_per_stage"]):
            stride = 2 if (s > 0 and b == 0) else 1
            size //= stride
            conv(f"s{s}b{b}_c1", c_prev, c, 3, size)
            conv(f"s{s}b{b}_c2", c, c, 3, size)
            if stride != 1 or c_prev != c:
                conv(f"s{s}b{b}_proj", c_prev, c, 1, size)
            c_prev = c
    layers.append(("out", c_prev * cfg["num_classes"]))
    return layers


def resnet_train_flops_per_sample(cfg: dict) -> float:
    """Forward + backward of every matmul-like layer: 2 FLOPs per
    multiply-add, x3 (forward, input gradient, weight gradient), less the
    stem's input gradient, which nothing needs."""
    layers = resnet_conv_layers(cfg)
    macs = sum(m for _, m in layers)
    return 2.0 * (3 * macs - layers[0][1])


def resnet_params(cfg: dict) -> int:
    """Conv and classifier weights plus batch-norm gain and bias."""
    n = 0
    c_prev = cfg["stem_channels"]
    n += 9 * cfg["image_channels"] * c_prev + c_prev + 2 * c_prev
    for s, c in enumerate(cfg["stage_channels"]):
        for b in range(cfg["blocks_per_stage"]):
            stride = 2 if (s > 0 and b == 0) else 1
            n += 9 * c_prev * c + c + 2 * c
            n += 9 * c * c + c + 2 * c
            if stride != 1 or c_prev != c:
                n += c_prev * c + c
            c_prev = c
    return n + c_prev * cfg["num_classes"] + cfg["num_classes"]
