"""Median, over the window's rounds that decoded and prefilled nothing,
of ``decode.dispatch`` + ``decode.fetch``: the call of the decode
program until its tokens are on the host, which is what the host waits
on the device. Program spans; ``round_host_ms`` is the rest of the
round."""

from perf.span_ring import decode_rounds
from perf.stats import median


def read(record):
    rounds = decode_rounds(record)
    return None if rounds is None else \
        1e3 * median(wait for _, wait in rounds)
