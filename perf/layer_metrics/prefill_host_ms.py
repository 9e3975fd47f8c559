"""Median, over the window's rounds that prefilled, of the
``engine.round`` span less what the host waited on the device in it
(``prefill.dispatch`` + ``prefill.fetch`` + ``decode.dispatch`` +
``decode.fetch``): schedule, pack, both stages, both commits. The prefill
twin of ``round_host_ms``; ``prefill_round_ms`` is the same rounds whole,
from outside. Program spans."""

from perf.span_account import DECODE_ONLY, rounds
from perf.stats import median


def read(record):
    every = rounds(record)
    if every is None:
        return None
    host = [r.seconds - r.waited for r in every if r.kind != DECODE_ONLY]
    return 1e3 * median(host) if host else None
