"""Median, over the window's rounds that decoded and prefilled nothing,
of the ``engine.round`` span less what the host waited on the device
(``decode.dispatch`` + ``decode.fetch``): schedule, stage, commit and
the client's bookkeeping inside ``engine.step``. Program spans."""

from perf.span_ring import decode_rounds
from perf.stats import median


def read(record):
    rounds = decode_rounds(record)
    return None if rounds is None else \
        1e3 * median(whole - wait for whole, wait in rounds)
