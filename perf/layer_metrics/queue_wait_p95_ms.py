"""95th percentile of ``request.queue`` (``enqueue_wall`` to
``admitted_wall``) over the requests that entered the engine inside the
window. Near zero in a closed loop, where a lane is free when a client
sends; it is the number the open-loop cells exist for. Program spans."""

from perf.span_ring import serve_window
from perf.stats import percentile


def read(record):
    cut = serve_window(record)
    if cut is None:
        return None
    t_open, t_close, records = cut
    waits = [1e3 * (r.t1 - r.t0) for r in records
             if r.name == "request.queue" and t_open <= r.t0 <= t_close]
    return percentile(waits, 95)
