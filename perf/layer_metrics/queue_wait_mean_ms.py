"""Mean of ``request.queue`` (``enqueue_wall`` to ``admitted_wall``) over
the requests that entered the engine inside the window and finished: the
first of the three parts of ``ttft_mean_ms`` the program records
(``perf/span_account.py``). ``queue_wait_p95_ms`` reads one other
request's prefill round where 5% of them queue and ~0.01 ms where fewer
do; the judged quantity is a mean, and so is this. Program spans."""

from perf.span_account import first_token_parts, mean_ms


def read(record):
    parts = first_token_parts(record)
    return None if parts is None else mean_ms(parts["queue"])
