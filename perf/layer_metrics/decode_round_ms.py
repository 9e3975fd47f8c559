"""Median host clock around ``engine.step`` for rounds that prefilled
nothing: stage + decode dispatch + fetch + host planning."""

from perf.stats import median


def read(record):
    rounds = [r["t1"] - r["t0"] for r in record["rounds"]
              if not r["prefilled"] and r["decoded_slots"]]
    return 1e3 * median(rounds) if rounds else None
