"""Mean, over every finished request sent inside the window, of the time
from the instant its client sent it to the return of the round that
produced its first token (harness clock)."""

import statistics


def read(record):
    waits = [1e3 * (r["first"] - r["sent"]) for r in record["requests"]
             if r["finish"] is not None]
    return statistics.fmean(waits) if waits else None
