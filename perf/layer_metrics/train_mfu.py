"""Model FLOP/s utilisation of the whole step at the median steady chunk:
6 * N * tokens / seconds / (chips * published bf16 peak). Not a kernel's
roofline share."""

from perf.stats import median, mfu_percent


def read(record):
    if not record["peak"]:
        return None
    seconds = median(c["seconds"] for c in record["chunks"])
    return mfu_percent(record["n_params"],
                       record["chunk_steps"] * record["tokens_per_step"],
                       seconds, record["chips"],
                       record["peak"]["bf16_flops_per_s"])
