"""Mean, over the window's rounds that decoded and prefilled nothing, of
the held experts that received a token / the held experts (all expert
layers), from ``engine.round``'s ``experts_touched`` and
``experts_held``: ``experts_touched_mean``'s quantity for a family whose
rounds carry the expert counters and none of MiMo's page counters (the
accepted reader picks its rounds by those too). With uniform routing of
32 tokens x 8 over 192 experts it is 1 - (23/24)**32 = 74%. Nothing
where the program records no such counter. Program counters."""

from perf.span_ring import serve_window

_KEYS = ("experts_touched", "experts_held", "expert_tokens_sum")


def decode_round_counts(record):
    """The attributes of every decode-only ``engine.round`` inside the
    window that carries the expert counters, or None where the program
    records none."""
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
