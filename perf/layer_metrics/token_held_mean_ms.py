"""Mean, over the requests that entered the engine inside the window and
finished, of the time the engine held a first token it already knew: the
``t1`` of the ``engine.round`` that holds the end of the request's
``request.prefill``, less that end. The round goes on to stage, dispatch,
fetch and commit its decode step before ``engine.step`` returns and the
caller can see the token: the third part of ``ttft_mean_ms``
(``perf/span_account.py``). Program spans."""

from perf.span_account import first_token_parts, mean_ms


def read(record):
    parts = first_token_parts(record)
    return None if parts is None else mean_ms(parts["held"])
