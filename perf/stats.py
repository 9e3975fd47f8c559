"""The benchmark's own arithmetic: percentiles, spreads, parameter
counts and model FLOP/s utilisation. Copied, not imported, so that a
change to the program cannot move the yardstick (``bench.py`` keeps the
same ``6 * N * tokens / seconds / peak``)."""

import math
import statistics


def median(values):
    return statistics.median(values)


def percentile(values, q):
    """The ``q``-th percentile (0-100), linear between order statistics
    (numpy's default), over ALL values given. None when empty."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def iqr_share(values):
    """Distance between the first and third quartile, as the builder's
    contract takes them (``statistics.quantiles(values, n=4)``), as a
    share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def gpt2_params(config, padded_vocab):
    """Parameters of a GPT-2 stack with tied embeddings: token and
    position tables, per layer 12h^2 + 13h (qkv, proj, two MLP matrices,
    their biases, two LayerNorms), and the final LayerNorm."""
    h, layers = config["n_embd"], config["n_layer"]
    return (padded_vocab * h + config["n_positions"] * h
            + layers * (12 * h * h + 13 * h) + 2 * h)


def mfu_percent(n_params, tokens, seconds, chips, peak_flops):
    """Model FLOP/s utilisation of a whole training step: 6 FLOP per
    parameter per token (forward + backward, nothing recomputed counts)
    over the published peak of every chip used. Not a kernel's roofline
    share."""
    return 100.0 * 6.0 * n_params * tokens / seconds / (chips * peak_flops)
