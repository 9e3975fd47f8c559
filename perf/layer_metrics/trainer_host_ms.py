"""Median over the steady chunks of ``trainer.chunk`` less
``chunk.fetch``: the loop is serial, so the time outside the fetch
(enqueueing the next chunk, the save check, the record, the log line) is
time in which the chip has nothing to run. Program spans; compare with
``idle_share.train`` x chunk seconds."""

from perf.span_ring import train_chunks
from perf.stats import median


def read(record):
    chunks = train_chunks(record)
    if chunks is None:
        return None
    return 1e3 * median(r.t1 - r.t0 - kids["chunk.fetch"]
                        for r, kids in chunks[1])
