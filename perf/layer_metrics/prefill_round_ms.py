"""Median host clock around rounds that prefilled (the round holds its
decode dispatch too: compare with ``decode_round_ms``)."""

from perf.stats import median


def read(record):
    rounds = [r["t1"] - r["t0"] for r in record["rounds"] if r["prefilled"]]
    return 1e3 * median(rounds) if rounds else None
