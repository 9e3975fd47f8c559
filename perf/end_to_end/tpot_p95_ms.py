"""95th percentile, over every finished request sent inside the window
with at least two tokens, of (finish - first token) / (tokens - 1)."""

from perf.stats import percentile


def read(record):
    gaps = [1e3 * (r["finish"] - r["first"]) / (r["tokens"] - 1)
            for r in record["requests"]
            if r["finish"] is not None and r["tokens"] >= 2]
    return percentile(gaps, 95)
