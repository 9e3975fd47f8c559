"""95th percentile, over every finished request sent inside the window,
of the time from the instant its client sent it to the return of the
round that produced its first token (harness clock). Not bounded: a
prefill is one fixed 1024-token dispatch, so the wait is one of a few
discrete values (one prefill batch in the request's round, or two), and
the p95 of some 90 requests flips between them with the share of
requests in the slower mode (1-8%, by the seed's order). The mean of the
same waits, ``ttft_mean_ms``, is the end-to-end metric."""

from perf.stats import percentile


def read(record):
    waits = [1e3 * (r["first"] - r["sent"]) for r in record["requests"]
             if r["finish"] is not None]
    return percentile(waits, 95)
