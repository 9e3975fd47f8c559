"""The bytes and operations the Falcon-H1 family's recurrence has to
move, from shapes and counters: what the roofline shares of
``serve-falconh1-decode`` divide by the measured device time. Kept with
the benchmark, so that every PR counts alike whatever implements the
layer: the work counted is the recurrence's OWN (``4 H P N`` a token a
layer: the decay and the outer product into the state, the contraction
with ``C``), not a chunked form's, so a change of chunk or of algorithm
does not move the yardstick; a vector is counted at the activations'
width (bfloat16), the state at its dtype, each once."""

BF16 = 2
_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_shapes(config):
    """What the counts need of a configuration, as plain numbers."""
    heads, head_dim = config["mamba_n_heads"], config["mamba_d_head"]
    return {
        "layers": config["num_hidden_layers"],
        "ssm_heads": heads,
        "ssm_head_dim": head_dim,
        "ssm_state": config["mamba_d_state"],
        "ssm_groups": config["mamba_n_groups"],
        "state_bytes": _DTYPE_BYTES[config.get("cache_dtype", "bfloat16")],
    }


def state_elements(shapes):
    """One slot's state in one layer: ``H x P x N``."""
    return shapes["ssm_heads"] * shapes["ssm_head_dim"] * shapes["ssm_state"]


def recurrence_flops(shapes, tokens):
    """``tokens`` steps of the recurrence in every layer: ``4 H P N``
    each."""
    return 4 * state_elements(shapes) * tokens * shapes["layers"]


def _vector_bytes(shapes):
    """A token's vectors into and out of the recurrence: ``x`` (H x P),
    ``B`` and ``C`` (G x N each), ``dt`` (H), ``y`` (H x P)."""
    H, P = shapes["ssm_heads"], shapes["ssm_head_dim"]
    return (2 * H * P + 2 * shapes["ssm_groups"] * shapes["ssm_state"]
            + H) * BF16


def decode_bytes(shapes, slots):
    """A decode round over ``slots`` live slots: every slot's state read
    and written once a layer, and its token's vectors."""
    return slots * shapes["layers"] * (
        2 * state_elements(shapes) * shapes["state_bytes"]
        + _vector_bytes(shapes))


def prefill_bytes(shapes, tokens, segments):
    """A prefill dispatch of ``tokens`` rows in ``segments`` sequences:
    every token's vectors, and each sequence's final state written once
    a layer."""
    return shapes["layers"] * (
        tokens * _vector_bytes(shapes)
        + segments * state_elements(shapes) * shapes["state_bytes"])
