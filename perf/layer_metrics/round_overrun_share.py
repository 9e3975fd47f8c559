"""Share of the window lost to rounds that ran far over: the sum, over
the rounds that took more than twice the median of their kind
(decode-only; prefilled, by its dispatches' ``trunk_rows``; a kind of
fewer than 5 rounds has no median to hold a round to), of the round less
that median, over the window. 0.0 where no round overran.
``perf/span_account.py`` names the span that held each. One reader for
``round_overrun_share`` and ``round_overrun_share.ttft``. Program
spans."""

from perf.span_account import overrun_share


def read(record):
    return overrun_share(record)
