"""What the traffic lets the selection spare: over the window's rounds
that decoded, the rows the full layers' attention read
(``sparse_rows_selected``: a slot's ``index_topk`` best, or all it has) /
the context rows their indexer scored (``index_rows_scored``), from
``engine.round``. 100% while no context is past ``index_topk``; 2,048 /
context beyond. Nothing where the program records no such counter.
Program counters."""

from perf.span_ring import serve_window


def read(record):
    cut = serve_window(record)
    if cut is None:
        return None
    t_open, t_close, records = cut
    rounds = [r.attrs for r in records if r.name == "engine.round"
              and r.t0 >= t_open and r.t1 <= t_close and r.attrs
              and r.attrs.get("index_rows_scored")]
    if not rounds:
        return None
    return 100.0 * sum(r["sparse_rows_selected"] for r in rounds) \
        / sum(r["index_rows_scored"] for r in rounds)
