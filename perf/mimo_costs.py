"""The bytes a MiMo decode round has to move, from shapes and counters:
what the roofline shares of ``serve-mimo-decode`` divide by the measured
device time. Kept with the benchmark, so that every PR counts alike
whatever implements the layer."""

BF16 = 2


def model_shapes(config):
    """What the byte counts need of a configuration, as plain numbers."""
    pattern = config["hybrid_layer_pattern"]
    return {
        "hidden": config["hidden_size"],
        "expert_width": config["moe_intermediate_size"],
        "expert_layers": sum(config["moe_layer_freq"]),
        "held": config["held_experts"][1],
        "global_layers": pattern.count(0),
        "window_layers": pattern.count(1),
        "global_row_bytes": config["num_key_value_heads"] * BF16 * (
            config["head_dim"] + config["v_head_dim"]),
        "window_row_bytes": config["swa_num_key_value_heads"] * BF16 * (
            config["swa_head_dim"] + config["swa_v_head_dim"]),
    }


def experts_bytes(shapes, experts_touched, assignments):
    """One decode round's grouped expert matmuls: the three matrices of
    every held expert that received a token (``experts_touched``, summed
    over the expert layers), plus the activations of the ``assignments``
    those experts served: the row read by gate and by up, their two
    results, the product read by down, its result."""
    H, F = shapes["hidden"], shapes["expert_width"]
    weights = experts_touched * 3 * H * F * BF16
    rows = assignments * BF16 * (2 * H + 2 * F + F + H)
    return weights + rows


def attend_bytes(shapes, page_size, global_pages, window_pages):
    """One decode round's attention reads: each live page of K and V,
    unpadded, once a layer of its kind. ``global_pages``: pages holding
    context, summed over the slots; ``window_pages``: ring pages that
    hold part of a slot's window, summed over the slots."""
    return page_size * (
        global_pages * shapes["global_row_bytes"] * shapes["global_layers"]
        + window_pages * shapes["window_row_bytes"]
        * shapes["window_layers"])
