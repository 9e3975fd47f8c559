"""Median over the steady chunks of chunk seconds / steps (one dispatch of
the jitted chunk: model, loss scaling, fused Adam)."""

from perf.stats import median


def read(record):
    return 1e3 * median(c["seconds"] for c in record["chunks"]) \
        / record["chunk_steps"]
