"""Mean, over the window's rounds that decoded and prefilled nothing, of
the held experts that received a token / the held experts (all expert
layers), from ``engine.round``'s ``experts_touched`` and
``experts_held``. With uniform routing of 64 tokens x 8 over 256 experts
it is 1 - (31/32)**64 = 87%. Program counters."""

from perf.span_ring import serve_window

_KEYS = ("experts_touched", "experts_held", "expert_tokens_sum",
         "global_pages_live", "window_pages")


def decode_round_counts(record):
    """The attributes above of every decode-only ``engine.round`` inside
    the window, or None where the program records none."""
    cut = serve_window(record)
    if cut is None:
        return None
    t_open, t_close, records = cut
    out = [r.attrs for r in records if r.name == "engine.round"
           and r.t0 >= t_open and r.t1 <= t_close and r.attrs
           and not r.attrs.get("prefilled") and r.attrs.get("decoded")
           and all(key in r.attrs for key in _KEYS)]
    return out or None


def read(record):
    counts = decode_round_counts(record)
    if not counts:
        return None
    return 100.0 * sum(c["experts_touched"] / c["experts_held"]
                       for c in counts) / len(counts)
