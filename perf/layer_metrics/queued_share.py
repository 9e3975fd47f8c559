"""Share of the requests that entered the engine inside the window and
finished whose ``request.queue`` carries ``rounds`` >= 1: an ``admit``
call left them queued at least once (``blocked`` says why). 0.0 where
none queued; nothing where the program does not count the rounds (a
parent commit). Program counters."""

from perf.span_account import requests


def read(record):
    joined = requests(record)
    if not joined or any("rounds" not in (q.attrs or {})
                         for q, _, _ in joined):
        return None
    return 100.0 * sum(1 for q, _, _ in joined if q.attrs["rounds"]) \
        / len(joined)
