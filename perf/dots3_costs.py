"""The bytes and operations the dots3-note family's attention has to
move, from shapes and counters: what the roofline shares of
``serve-dots3-longcontext`` divide by the measured device time. Kept with
the benchmark, so that every PR counts alike whatever implements the
layer: a cache row is counted at its live columns however the program
pads it, a selected row ONCE however it is gathered, the prefill's
attention in the expanded form over the selected keys alone (the least
arithmetic an exact form does: a masked dense form does more)."""

BF16 = 2


def model_shapes(config):
    """What the counts need of a configuration, as plain numbers (the
    expert layer's under the names ``mimo_costs.experts_bytes`` takes)."""
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    sliding = sum(t == "sliding_attention" for t in config["layer_types"])
    return {
        "hidden": config["hidden_size"],
        "expert_width": config["moe_intermediate_size"],
        "expert_layers": layers - dense,
        "held": config["held_experts"][1],
        "layers": layers,
        "full_layers": layers - sliding,
        "sliding_layers": sliding,
        "heads": config["num_attention_heads"],
        "qk_width": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        "v_width": config["v_head_dim"],
        "latent_rank": config["kv_lora_rank"],
        "latent_width": config["kv_lora_rank"] + config["qk_rope_head_dim"],
        "index_heads": config["index_n_heads"],
        "index_width": config["index_head_dim"],
        "index_topk": config["index_topk"],
        "window": config["sliding_window_size"],
        "window_heads": config["swa_num_attention_heads"],
        "window_rank": config["swa_kv_lora_rank"],
        "window_width": config["swa_kv_lora_rank"]
        + config["swa_qk_rope_head_dim"],
    }


def index_flops(shapes, pairs):
    """The indexer's arithmetic for ``pairs`` (query, key) pairs: every
    index head's product over the key's width, a multiply and an add, a
    full layer."""
    return pairs * shapes["index_heads"] * shapes["index_width"] * 2 \
        * shapes["full_layers"]


def sparse_prefill_flops(shapes, index_pairs, sparse_pairs):
    """A prefill dispatch's selection and attention in the full layers:
    the indexer over every causal pair, then every head's score and value
    sum in the EXPANDED form over the selected pairs alone."""
    return index_flops(shapes, index_pairs) + sparse_pairs * shapes["heads"] \
        * (shapes["qk_width"] + shapes["v_width"]) * 2 * shapes["full_layers"]


def sparse_decode_bytes(shapes, rows_scored, rows_selected):
    """A decode round's reads in the full layers: the index key of every
    context row and the latent row of every selected one, once a layer."""
    return (rows_scored * shapes["index_width"]
            + rows_selected * shapes["latent_width"]) * BF16 \
        * shapes["full_layers"]


def sparse_decode_flops(shapes, rows_scored, rows_selected):
    """The same rows' arithmetic: the indexer over every context row,
    the absorbed form (score over the row's width, sum over its value
    columns) over the selected ones."""
    return index_flops(shapes, rows_scored) + rows_selected \
        * shapes["heads"] * 2 * (shapes["latent_width"]
                                 + shapes["latent_rank"]) \
        * shapes["full_layers"]


def window_bytes(shapes, rows):
    """A decode round's reads in the sliding layers: each ring row
    inside a slot's window, its live columns, once a layer."""
    return rows * shapes["window_width"] * BF16 * shapes["sliding_layers"]
