"""What a decode step of a model whose indexer scores pooled keys must move
and compute, from its shapes: the functions behind ``pooled_index_roofline``
and ``nope_attend_roofline`` (``glm-5.3-flash-l5``; the keys are the
catalog's). ``lib/sparse_cost.py`` counts an index key a position and takes
every entry of ``indexer_types`` for a latent layer; here a key stands for
``index_kpool`` positions and the latent layers are
``linear_attn_config.full_attn_layers``.

``pools_scored`` and ``keys_attended`` are the server's own counts of a
decode step (``serve.decode``'s attrs): pools the live slots' queries scored
(``cursor // index_kpool`` a slot and latent layer) and latent rows they
attended (the selected pools' rows and the tail).
"""

WEIGHT_BYTES = 4        # float32 storage (PERF.md section 7)
CACHE_BYTES = 2         # bf16 latent rows and pooled index keys
SUM_BYTES = 4           # the open pool's running sum, float32


def latent_layers(cfg):
    """Layers with latent attention and an indexer of their own."""
    return len(cfg["linear_attn_config"]["full_attn_layers"])


def indexer_weight_bytes(cfg):
    """One latent layer's indexer as stored: ``wq`` [rq, hI dI], ``wk``
    [D, dI], ``ww`` [D, hI] and the key norm's gain and bias."""
    d, rq = cfg["hidden_size"], cfg["q_lora_rank"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return WEIGHT_BYTES * (rq * hi * di + d * di + d * hi + 2 * di)


def index_step_bytes(cfg, *, pools_scored, live):
    """Bytes the ``dsa.index`` and ``dsa.pool`` parts of one decode step
    move: the pooled keys the live slots' queries score, the indexer weights
    of every latent layer as stored, and the rows (each live slot's
    compressed query and normed input in; the open pool's sum read and
    written and its row written; ``index_topk + index_kpool`` selected
    positions out)."""
    n = latent_layers(cfg)
    di = cfg["index_head_dim"]
    rows = live * n * (
        CACHE_BYTES * (cfg["q_lora_rank"] + cfg["hidden_size"] + di)
        + 2 * SUM_BYTES * di
        + 4 * (cfg["index_topk"] + cfg["index_kpool"]))
    return (pools_scored * di * CACHE_BYTES + n * indexer_weight_bytes(cfg)
            + rows)


def index_step_flops(cfg, *, pools_scored, live):
    """Operations of the same: every scored pool against ``index_n_heads``
    query heads, and the live rows' three projections."""
    d, rq = cfg["hidden_size"], cfg["q_lora_rank"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return (2 * hi * di * pools_scored
            + 2 * live * latent_layers(cfg) * (rq * hi * di + d * di + d * hi))


def attend_step_bytes(cfg, *, keys_attended, live):
    """Bytes the ``mla.attend`` part of one decode step moves: the selected
    rows and the tail as stored (``kv_lora_rank`` lanes a row: no rotary
    part, no padding), ``wukv`` of every latent layer as stored, and the
    rows (each live slot's query heads in, its attention output out)."""
    n, h = latent_layers(cfg), cfg["num_attention_heads"]
    picked = keys_attended * cfg["kv_lora_rank"] * CACHE_BYTES
    wukv = WEIGHT_BYTES * cfg["kv_lora_rank"] * h * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    rows = live * n * CACHE_BYTES * h * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    return picked + n * wukv + rows
