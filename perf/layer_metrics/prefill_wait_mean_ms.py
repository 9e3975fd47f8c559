"""Mean of ``request.prefill`` (``admitted_wall`` to ``first_token_wall``,
the stamp the prefill's fetch took) over the requests that entered the
engine inside the window and finished: the second part of
``ttft_mean_ms``, the request's own prefill round up to the instant its
first token is known (``perf/span_account.py``). Program spans."""

from perf.span_account import first_token_parts, mean_ms


def read(record):
    parts = first_token_parts(record)
    return None if parts is None else mean_ms(parts["prefill"])
