"""What a decode step of a model with learned sparse attention must move, from
its shapes: the functions behind ``dsa_index_roofline`` and
``sparse_attend_roofline`` (``glm-5.2-l5``; the keys are the catalog's).

Both parts wait for memory, not arithmetic. A decode step's indexer multiplies
a slot's cached index keys once (32 heads x 128 x 2 FLOP a key: 8 KFLOP against
256 B) and its projections read float32 matrices for 16 rows; the attention
over the selection reads 2,048 latent rows of 1,280 B a slot and layer and
``wukv`` as stored, and computes 64 heads x 2,048 keys x (576 + 512) x 2 = 285
MFLOP a slot and layer (0.02 ms at the chip's peak against 2.6 MB).

``keys_cached`` and ``keys_attended`` are the server's own counts of a decode
step (``serve.decode``'s attrs): latent rows the live slots hold up to their
cursors, and rows their queries attend, each summed over the 'mla' layers.
"""

WEIGHT_BYTES = 4        # float32 storage (PERF.md section 7)
CACHE_BYTES = 2         # bf16 latent rows and index keys


def layers(cfg):
    """``(mla layers, layers with a full indexer)`` of the kept stack."""
    return len(cfg["indexer_types"]), cfg["indexer_types"].count("full")


def latent_row_lanes(cfg):
    """Lanes a cached latent row takes: ``kv_lora_rank + qk_rope_head_dim``
    numbers and zeros up to a multiple of 128 (``kv_cache.latent_row_width``)."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def indexer_weight_bytes(cfg):
    """One full layer's indexer as stored: ``wq`` [rq, hI dI], ``wk`` [D, dI],
    ``ww`` [D, hI] and the key norm's gain and bias."""
    d, rq = cfg["hidden_size"], cfg["q_lora_rank"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return WEIGHT_BYTES * (rq * hi * di + d * di + d * hi + 2 * di)


def index_step_bytes(cfg, *, keys_cached, live):
    """Bytes the ``dsa.index`` part of one decode step moves: the live
    slots' index keys below their cursors in the layers with an indexer, those
    layers' indexer weights as stored, and the rows (each live slot's
    compressed query and normed input in, its new index key and its ``topk``
    selected positions out)."""
    n_mla, n_full = layers(cfg)
    keys = keys_cached / n_mla * n_full * cfg["index_head_dim"] * CACHE_BYTES
    rows = live * n_full * (
        CACHE_BYTES * (cfg["q_lora_rank"] + cfg["hidden_size"]
                       + cfg["index_head_dim"])
        + 4 * cfg["index_topk"])
    return keys + n_full * indexer_weight_bytes(cfg) + rows


def attend_step_bytes(cfg, *, keys_attended, live):
    """Bytes the ``mla.attend`` part of one decode step moves: the selected
    rows as stored (``keys_attended`` already sums min(cursor + 1, topk) over
    live slots and layers), ``wukv`` of every layer as stored, and the rows
    (each live slot's query heads in, its attention output out)."""
    n_mla, _ = layers(cfg)
    h = cfg["num_attention_heads"]
    picked = keys_attended * latent_row_lanes(cfg) * CACHE_BYTES
    wukv = WEIGHT_BYTES * cfg["kv_lora_rank"] * h * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    rows = live * n_mla * CACHE_BYTES * h * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return picked + n_mla * wukv + rows
