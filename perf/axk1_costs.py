"""The bytes and operations an A.X-K1 decode round has to move, from
shapes and counters: what the roofline shares of ``serve-axk1-longprompt``
divide by the measured device time. Kept with the benchmark, so that
every PR counts alike whatever implements the layer (a cache row is
counted at its 576 live columns, however the program pads it)."""

BF16 = 2


def model_shapes(config):
    """What the counts need of a configuration, as plain numbers (the
    expert layer's under the names ``mimo_costs.experts_bytes`` takes)."""
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    return {
        "hidden": config["hidden_size"],
        "expert_width": config["moe_intermediate_size"],
        "expert_layers": layers - dense,
        "held": config["held_experts"][1],
        "layers": layers,
        "heads": config["num_attention_heads"],
        "latent_rank": config["kv_lora_rank"],
        "latent_width": config["kv_lora_rank"] + config["qk_rope_head_dim"],
    }


def latent_bytes(shapes, page_size, pages_live):
    """One decode round's attention reads: each live latent page (pages
    of the pool holding context, summed over the slots), its live
    columns, ONCE a layer: the page is K and V both."""
    return pages_live * page_size * shapes["latent_width"] * BF16 \
        * shapes["layers"]


def latent_flops(shapes, context_tokens):
    """One decode round's attention arithmetic in the absorbed form:
    every head's score over the row's whole width and its sum over the
    value columns, a multiply and an add each, for every context token
    (summed over the slots), a layer."""
    return context_tokens * shapes["heads"] * 2 * (
        shapes["latent_width"] + shapes["latent_rank"]) * shapes["layers"]
